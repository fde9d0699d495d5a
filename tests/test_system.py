"""End-to-end system behaviour: training convergence, checkpoint/restart,
fault tolerance, compression, lookaside workflow, serving, traffic
routing — the integration layer of the paper's platform."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs.base import TrainConfig
from repro.configs.registry import get_config
from repro.core.lookaside import ControlMsg, LookasideBlock
from repro.core.memory import BufferPool
from repro.core.rdma import Opcode, RDMAEngine, WQE
from repro.core.streaming import (TrafficClass, TrafficRouter, TransferDesc,
                                  compress_bucket, decompress_bucket)
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.models import init_params
from repro.runtime.fault_tolerance import (ElasticController,
                                           HeartbeatMonitor,
                                           detect_stragglers,
                                           plan_elastic_mesh)
from repro.train import init_adam, make_train_step


def test_training_memorizes_tiny():
    cfg = get_config("tiny")
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=30,
                       remat=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_adam(params)
    step = jax.jit(make_train_step(cfg, tcfg))
    pipe = SyntheticPipeline(DataConfig(seed=0, vocab_size=cfg.vocab_size,
                                        batch=4, seq_len=32))
    b = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    losses = []
    for _ in range(20):
        loss, params, opt = step(params, opt, b)
        losses.append(float(loss))
    assert losses[-1] < 0.7 * losses[0], losses


def test_checkpoint_restart_bitexact():
    """Train 6 steps == train 3 + save/restore + 3 more, bit-exactly."""
    cfg = get_config("tiny")
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=10)
    pipe = SyntheticPipeline(DataConfig(seed=1, vocab_size=cfg.vocab_size,
                                        batch=2, seq_len=16))
    step = jax.jit(make_train_step(cfg, tcfg))

    def run(n0, n1, params, opt):
        for i in range(n0, n1):
            b = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
            _, params, opt = step(params, opt, b)
        return params, opt

    p0 = init_params(cfg, jax.random.PRNGKey(0))
    o0 = init_adam(p0)
    pa, _ = run(0, 6, p0, o0)

    pb, ob = run(0, 3, p0, o0)
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        cm.save(3, (pb, ob))
        (pr, orr), s = cm.restore((pb, ob))
        assert s == 3
        pb2, _ = run(3, 6, pr, orr)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_restore_with_target_shardings():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        cm.save(1, params)
        sh = jax.tree.map(
            lambda x: jax.sharding.SingleDeviceSharding(jax.devices()[0]),
            params)
        restored, _ = cm.restore(params, target_shardings=sh)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fault_tolerance_full_loop():
    t = [0.0]
    mon = HeartbeatMonitor(16, timeout=10, clock=lambda: t[0])
    ctl = ElasticController(mon, model_parallel=4, devices_per_host=4)
    for h in range(16):
        mon.beat(h)
    assert ctl.step(0) is None
    t[0] = 30.0
    for h in range(12):        # hosts 12..15 die
        mon.beat(h)
    plan = ctl.step(1)
    assert plan is not None
    assert plan.shape[-1] == 4                       # TP preserved
    assert plan.n_devices <= 12 * 4
    assert plan.n_devices % 4 == 0


def test_straggler_detection():
    times = {0: 1.0, 1: 1.1, 2: 0.9, 3: 5.0, 4: 1.0}
    assert detect_stragglers(times) == [3]
    assert detect_stragglers({0: 1.0}) == []


def test_elastic_mesh_math():
    plan = plan_elastic_mesh(alive_devices=300, model_parallel=16)
    assert plan.shape == (16, 16)                    # pow2 DP
    with pytest.raises(RuntimeError):
        plan_elastic_mesh(8, model_parallel=16)


def test_compression_error_feedback_converges():
    """Error feedback: accumulated compressed grads -> true grad."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
    residual = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    n = 20
    for _ in range(n):
        q, s, residual = compress_bucket(g, residual, chunk=256)
        acc = acc + decompress_bucket(q, s, g.shape)
    err = float(jnp.max(jnp.abs(acc / n - g)))
    scale = float(jnp.max(jnp.abs(g)))
    assert err < scale * 0.02, (err, scale)


def test_networked_matmul_workflow():
    """The paper's Fig 6 workflow end-to-end (see also examples/)."""
    from repro.kernels import ops as kops
    eng = RDMAEngine(n_peers=2, pool_size=8192)
    lc = LookasideBlock(eng, peer=1)     # the LC block rides peer 1's NIC
    m = 8
    data_pool = BufferPool(eng, 0)      # peer1 in the paper (holds data)
    smart_pool = BufferPool(eng, 1)     # peer2 = RecoNIC (computes)
    a_src = data_pool.alloc(m * m)
    b_src = data_pool.alloc(m * m)
    rng = np.random.default_rng(0)
    A = rng.normal(size=(m, m)).astype(np.float32)
    B = rng.normal(size=(m, m)).astype(np.float32)
    data_pool.write(a_src, A.reshape(-1))
    data_pool.write(b_src, B.reshape(-1))

    a_dst = smart_pool.alloc(m * m)
    b_dst = smart_pool.alloc(m * m)
    c_dst = smart_pool.alloc(m * m)
    qp = eng.create_qp(1, 0)
    _ = eng.create_qp(0, 1)

    # (2)(3) WQEs + one doorbell  (4)(5) poll completions
    eng.post_send(qp, WQE(Opcode.READ, qp.qp_num, 1, local_addr=a_dst.base,
                          remote_addr=a_src.base, length=m * m,
                          rkey=a_src.rkey))
    eng.post_send(qp, WQE(Opcode.READ, qp.qp_num, 2, local_addr=b_dst.base,
                          remote_addr=b_src.base, length=m * m,
                          rkey=b_src.rkey))
    eng.ring_sq_doorbell(qp)
    assert len(eng.poll_cq(qp)) == 2

    # (6) control message -> systolic MM kernel  (7) completion
    # (kernel sees an LCContext: local dev_mem via load/store, remote
    # memory only through WQEs on its own QPs)
    def mm_kernel(ctx, a_addr, b_addr, c_addr, mm):
        x = ctx.load(a_addr, mm * mm).reshape(mm, mm)
        y = ctx.load(b_addr, mm * mm).reshape(mm, mm)
        z = np.asarray(kops.matmul(jnp.asarray(x), jnp.asarray(y)))
        ctx.store(c_addr, z.reshape(-1))
        return c_addr

    lc.register(7, mm_kernel, "systolic_mm")
    lc.dispatch(ControlMsg(7, (a_dst.base, b_dst.base, c_dst.base, m),
                           tag=1))
    st = lc.poll(7)
    assert st.ok
    # (8) result correct
    C = smart_pool.read(c_dst).reshape(m, m)
    np.testing.assert_allclose(C, A @ B, rtol=1e-4, atol=1e-4)


def test_traffic_router_telemetry():
    r = TrafficRouter()
    routed = {}
    r.register_path("offloaded", lambda b: routed.setdefault("o", len(b)))
    r.register_path("host", lambda b: routed.setdefault("h", len(b)))
    descs = [TransferDesc(TrafficClass.BULK_GRAD, 1000),
             TransferDesc(TrafficClass.KV_PAGE, 500),
             TransferDesc(TrafficClass.HOST_IO, 10),
             TransferDesc(TrafficClass.CTRL, 1)]
    out = r.route(descs)
    assert out == {"offloaded": 2, "host": 2}
    assert r.counters[TrafficClass.BULK_GRAD]["bytes"] == 1000


def test_kv_page_migration():
    from repro.serve.kv_cache import PagedKVPool, migrate_sequence
    eng = RDMAEngine(n_peers=2, pool_size=4096)
    router = TrafficRouter()
    src = PagedKVPool(eng, 0, page_elems=64, max_pages=8)
    dst = PagedKVPool(eng, 1, page_elems=64, max_pages=8)
    rng = np.random.default_rng(0)
    pages_data = []
    for _ in range(3):
        p = src.append_page(seq_id=42)
        d = rng.normal(size=64).astype(np.float32)
        src.write_page(p, d)
        pages_data.append(d)
    qp = eng.create_qp(1, 0)
    _ = eng.create_qp(0, 1)
    d0 = eng.transport.dispatch_count
    n = migrate_sequence(eng, router, src, dst, 42, qp)
    assert n == 3
    assert eng.transport.dispatch_count - d0 == 1    # ONE doorbell batch
    assert src.seq_len_pages(42) == 0
    for i, page in enumerate(dst.pages[42]):
        np.testing.assert_array_equal(dst.read_page(page), pages_data[i])
    assert router.counters[TrafficClass.KV_PAGE]["count"] == 3


def test_data_pipeline_determinism_and_skip_ahead():
    p = SyntheticPipeline(DataConfig(seed=9, batch=2, seq_len=8))
    direct = p.batch_at(7)
    resumed = next(p.resume_from(7))
    np.testing.assert_array_equal(direct["tokens"], resumed["tokens"])
    np.testing.assert_array_equal(p.batch_at(0)["labels"][:, :-1],
                                  p.batch_at(0)["tokens"][:, 1:])


@pytest.mark.parametrize("env_dir", ["", "/elsewhere/jax-cache"])
def test_compile_cache_location(monkeypatch, env_dir):
    """Entry points keep JAX's compile cache where the environment says,
    and otherwise at one fixed path inside the checkout."""
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert compile_cache.enable_compile_cache() == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
