"""Collective conformance: gradient-bucket all-reduce as scheduled RDMA
verbs (train.collectives) vs the ``jax.lax.psum`` oracle.

Pins the PR's hard claims: byte-identical reductions across algorithms,
dtype mixes, and non-pow2 peer counts; zero steady-state compiles; byte
parity under seeded drop (retransmits reuse the warmed shape buckets);
and DRR fairness — a streaming gradient collective must not skew service
between equal-weight serving tenants (Jain == 1.0).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rdma.cost_model import jain_fairness_index
from repro.core.rdma.engine import RDMAEngine
from repro.core.rdma.reliability import FaultInjector
from repro.core.rdma.verbs import Opcode, WQE
from repro.train.collectives import (CollectiveError, RDMACollective,
                                     ideal_wire_words)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def _engine(n: int, pool: int = 1 << 14, **kw) -> RDMAEngine:
    return RDMAEngine(n_peers=max(n, 2), pool_size=pool, **kw)


def _psum_oracle(shards) -> np.ndarray:
    """All-reduce oracle: vmap over a named axis — the same lax.psum the
    abstract bucketed path uses, no multi-device mesh needed."""
    stacked = jnp.stack([jnp.asarray(s, jnp.float32) for s in shards])
    return np.asarray(jax.vmap(lambda x: jax.lax.psum(x, "p"),
                               axis_name="p")(stacked))


def _int_shards(rng, n: int, words: int):
    """Integer-valued f32 shards: sums are exact under ANY reduction
    order, so parity checks can demand bitwise equality."""
    return [rng.integers(-8, 9, words).astype(np.float32)
            for _ in range(n)]


@pytest.mark.parametrize("n", [2, 3, 4, 5, 8])
@pytest.mark.parametrize("algorithm", ["ring", "rd"])
def test_allreduce_parity(n, algorithm):
    """Byte parity vs psum across pow2 and non-pow2 peer counts, and the
    wire-byte ledger must match the α–β ideal exactly."""
    rng = np.random.default_rng(n)
    words = 100                        # non-multiple of n: padding path
    eng = _engine(n)
    coll = RDMACollective(eng, n, algorithm=algorithm)
    shards = _int_shards(rng, n, words)
    got = coll.all_reduce(shards)
    want = _psum_oracle(shards)
    for p in range(n):
        assert np.array_equal(got[p][:words], want[p]), (algorithm, n, p)
    assert coll.stats["wire_bytes"] == 4 * ideal_wire_words(
        algorithm, n, words)


def test_allreduce_parity_dtype_mix():
    """Grad pytrees mix fp32/bf16/int8 leaves; all land in f32 pool words
    and the reduction stays exact for integer-valued payloads."""
    rng = np.random.default_rng(0)
    n = 4
    leaves = {
        "w": (np.float32, 96), "h": (jnp.bfloat16, 64),
        "r": (np.int8, 32),
    }
    per_peer = []
    for p in range(n):
        vecs = [np.asarray(
            jnp.asarray(rng.integers(-4, 5, size), dt), np.float32)
            for dt, size in leaves.values()]
        per_peer.append(np.concatenate(vecs))
    eng = _engine(n)
    coll = RDMACollective(eng, n)
    got = coll.all_reduce(per_peer)
    want = _psum_oracle(per_peer)
    words = per_peer[0].size
    for p in range(n):
        assert np.array_equal(got[p][:words], want[p])


def test_reduce_scatter_all_gather_pair():
    """The ZeRO-1 boundary: RS hands each peer its owned reduced chunk;
    AG of those chunks reconstructs the full sum everywhere."""
    rng = np.random.default_rng(1)
    n, words = 4, 128                  # multiple of n: no padding
    eng = _engine(n)
    coll = RDMACollective(eng, n)
    shards = _int_shards(rng, n, words)
    want = _psum_oracle(shards)
    chunks = coll.reduce_scatter(shards)
    cw = words // n
    for p in range(n):                 # peer p owns chunk (p+1) mod n
        own = (p + 1) % n
        assert np.array_equal(chunks[p], want[p][own * cw:(own + 1) * cw])
    full = coll.all_gather(chunks)
    for p in range(n):
        assert np.array_equal(full[p], want[p])


def test_zero_warm_compiles_across_steps():
    """Repeated steps ride cached descriptor programs: after the first
    all-reduce, later ones must add ZERO descriptor or QDMA compiles."""
    rng = np.random.default_rng(2)
    n = 4
    eng = _engine(n)
    coll = RDMACollective(eng, n)
    coll.all_reduce(_int_shards(rng, n, 256))          # warm-up
    c0 = eng.stats["transport"]["compiles"]
    q0 = eng.stats["transport"]["qdma_compiles"]
    for _ in range(3):
        coll.all_reduce(_int_shards(rng, n, 256))
    assert eng.stats["transport"]["compiles"] == c0
    assert eng.stats["transport"]["qdma_compiles"] == q0


def test_retransmit_under_seeded_drop_parity():
    """10% seeded drop: chunk READs retransmit go-back-N through the
    same shape buckets — byte parity and zero new compiles."""
    rng = np.random.default_rng(3)
    n = 3
    eng = _engine(n)
    eng.install_fault_injector(FaultInjector(7, drop=0.10))
    coll = RDMACollective(eng, n)
    shards = _int_shards(rng, n, 96)
    got = coll.all_reduce(shards)               # warm-up (faulted too)
    want = _psum_oracle(shards)
    for p in range(n):
        assert np.array_equal(got[p][:96], want[p])
    c0 = eng.stats["transport"]["compiles"]
    q0 = eng.stats["transport"]["qdma_compiles"]
    shards2 = _int_shards(rng, n, 96)
    got2 = coll.all_reduce(shards2)
    want2 = _psum_oracle(shards2)
    for p in range(n):
        assert np.array_equal(got2[p][:96], want2[p])
    rel = eng.stats.get("reliability", {})
    assert rel.get("retransmits", 0) > 0, "drop profile never fired"
    assert eng.stats["transport"]["compiles"] == c0
    assert eng.stats["transport"]["qdma_compiles"] == q0


def test_overlapped_flushes_with_multiple_buckets():
    """pipeline_depth=2 over 4 buckets: consecutive buckets' rounds must
    share flushes (the comm/compute overlap ledger)."""
    rng = np.random.default_rng(4)
    n = 2
    eng = _engine(n, pool=1 << 15)
    coll = RDMACollective(eng, n, pipeline_depth=2)
    buckets = [_int_shards(rng, n, 256) for _ in range(4)]
    got = coll.all_reduce_buckets(buckets)
    for b in range(4):
        want = _psum_oracle(buckets[b])
        for p in range(n):
            assert np.array_equal(got[b][p][:256], want[p])
    assert coll.stats["overlapped_flushes"] > 0
    assert coll.stats["flushes"] >= coll.stats["overlapped_flushes"]


def test_drr_serving_fairness_while_training_streams():
    """Collective QPs are ordinary DRR tenants: two equal-weight serving
    QPs streaming alongside a gradient all-reduce split the engine
    evenly (Jain over their service == 1.0)."""
    eng = _engine(2, pool=1 << 14, scheduler="drr", flush_budget=6)
    hi = eng.pool_size - 512            # serving arena, above collective
    eng.register_mr(0, hi, 256)
    src = eng.register_mr(1, hi, 256)
    qa = eng.create_qp(0, 1, weight=2)
    qb = eng.create_qp(0, 1, weight=2)
    for i in range(24):                 # equal backlogs, armed deferred
        for qp in (qa, qb):
            eng.post_send(qp, WQE(Opcode.READ, qp.qp_num, wr_id=9000 + i,
                                  local_addr=hi, remote_addr=src.base,
                                  length=4, rkey=src.rkey))
            eng.ring_sq_doorbell(qp, defer=True)
    rng = np.random.default_rng(5)
    coll = RDMACollective(eng, 2, weight=2, pipeline_depth=2)
    buckets = [_int_shards(rng, 2, 256) for _ in range(3)]
    got = coll.all_reduce_buckets(buckets)
    for b in range(3):
        want = _psum_oracle(buckets[b])
        assert np.array_equal(got[b][0][:256], want[0])
    served = [eng.stats["qp_service"].get(q.qp_num, 0) for q in (qa, qb)]
    assert served[0] > 0, "serving tenants never interleaved"
    assert jain_fairness_index(served) == 1.0, served


def test_collective_error_surfaces_statuses():
    """A peer failure mid-collective raises CollectiveError (terminal
    CQEs, not silent corruption)."""
    rng = np.random.default_rng(6)
    eng = _engine(2)
    inj = eng.install_fault_injector(FaultInjector(0))
    coll = RDMACollective(eng, 2, max_flushes=8)
    inj.stall_peer(1)
    with pytest.raises(CollectiveError):
        coll.all_reduce(_int_shards(rng, 2, 64))


def test_bucketize_bills_dtype_itemsize():
    """Regression (satellite 1): bucket planning must bill bf16 leaves 2
    bytes/elem and int8 1 — never a hardcoded 4."""
    from repro.train.train_step import _bucketize
    grads = {
        "a": jnp.zeros(100, jnp.float32),    # 400 B
        "b": jnp.zeros(100, jnp.bfloat16),   # 200 B
        "c": jnp.zeros(100, jnp.int8),       # 100 B
    }
    leaves, _, buckets = _bucketize(grads, 512)
    assert sum(b.bytes for b in buckets) == 700
    # old *4 billing would refuse to pair ANY two leaves under 512 B
    assert len(buckets) == 2, [b.bytes for b in buckets]


def test_compress_without_residuals_raises():
    """Regression (satellite 3): compress=True with no error-feedback
    state must raise, never silently ship uncompressed fp32."""
    from repro.train.train_step import bucketed_sync
    grads = {"w": jnp.ones(8, jnp.float32)}
    with pytest.raises(ValueError, match="residuals"):
        bucketed_sync(grads, ("data",), 1 << 20, compress=True,
                      residuals=None)


@pytest.mark.slow
def test_rdma_train_step_end_to_end():
    """sync='rdma': the bucketed train step's gradient sync rides the
    engine — loss decreases, zero warm compiles across steps."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.models import init_params
    from repro.train import init_adam
    from repro.train.train_step import make_bucketed_train_step
    cfg = get_config("tiny")
    tcfg = TrainConfig(remat=False, zero1=False, sequence_parallel=False,
                       grad_bucket_mb=0.0625)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_adam(params)
    step = make_bucketed_train_step(cfg, tcfg, None, sync="rdma",
                                    n_peers=2)
    batch = {"tokens": jnp.ones((4, 32), jnp.int32),
             "labels": jnp.ones((4, 32), jnp.int32)}
    loss1, p1, o1, _ = step(params, opt, batch, None)
    eng = step.collective(0).engine
    c0 = eng.stats["transport"]["compiles"]
    q0 = eng.stats["transport"]["qdma_compiles"]
    loss2, _, _, _ = step(p1, o1, batch, None)
    assert np.isfinite(float(loss1))
    assert float(loss2) < float(loss1), (float(loss1), float(loss2))
    assert eng.stats["transport"]["compiles"] == c0
    assert eng.stats["transport"]["qdma_compiles"] == q0
    assert eng.stats["collectives"]["overlapped_flushes"] > 0
    assert eng.stats["collectives"]["wire_bytes"] > 0


@pytest.mark.slow
def test_allreduce_parity_ici_transport():
    """Same parity claim on the REAL sharded-pool transport (4 forced
    host devices -> ICITransport), in a subprocess."""
    code = """
import numpy as np
from repro.core.rdma.engine import RDMAEngine
from repro.train.collectives import RDMACollective
rng = np.random.default_rng(0)
n = 4
eng = RDMAEngine(n_peers=n, pool_size=1 << 12)
assert type(eng.transport).__name__ == 'ICITransport', type(eng.transport)
coll = RDMACollective(eng, n)
shards = [rng.integers(-8, 9, 96).astype(np.float32) for _ in range(n)]
want = np.sum(shards, axis=0)
got = coll.all_reduce(shards)
for p in range(n):
    assert np.array_equal(got[p][:96], want), p
print('ICI_COLL_OK')
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert "ICI_COLL_OK" in r.stdout, r.stdout + r.stderr
