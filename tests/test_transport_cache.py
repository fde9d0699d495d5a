"""Descriptor-driven transport: compile-cache reuse across addresses,
coalescer semantics, deque completion paths, indexed responder lookup,
and ICITransport/LocalTransport parity (subprocess, forced multi-device).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.rdma import RDMAEngine, Opcode, WQE, coalesce_plan
from repro.core.rdma.verbs import QueuePair

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def _random_plan(rng, n_wqes, n_peers=2, pool=64):
    """Random transfers including loopback and overlapping ranges."""
    plan = []
    for _ in range(n_wqes):
        ln = int(rng.integers(1, 9))
        plan.append(("xfer", int(rng.integers(0, n_peers)),
                     int(rng.integers(0, n_peers)),
                     int(rng.integers(0, pool - ln)),
                     int(rng.integers(0, pool - ln)), ln))
    return plan


def _fresh_transports(rng, n_peers=2, pool=64):
    import jax.numpy as jnp
    from repro.core.rdma.transport import make_transport
    init = rng.standard_normal((n_peers, pool)).astype(np.float32)
    a = make_transport(n_peers, pool)
    b = make_transport(n_peers, pool)
    a.pool = jnp.asarray(init)
    b.pool = jnp.asarray(init)
    return a, b


class TestCompileCache:
    def test_same_shape_fresh_addresses_reuse_one_compile(self):
        """20 address-varying batches of one shape profile -> 1 miss."""
        import jax.numpy as jnp
        from repro.core.rdma.transport import make_transport
        rng = np.random.default_rng(0)
        t = make_transport(2, 256)
        t.pool = jnp.asarray(rng.standard_normal((2, 256)), jnp.float32)
        for i in range(20):
            sa, da = int(rng.integers(0, 96)), int(rng.integers(128, 224))
            t.execute_batch([("xfer", 0, 1, sa, da, 30)])
        assert t.stats["dispatches"] == 20
        assert t.stats["cache_misses"] == 1
        assert t.stats["cache_hits"] == 19
        assert t.stats["compiles"] == 1

    def test_shape_buckets_pow2(self):
        from repro.core.rdma.transport import shape_buckets
        assert shape_buckets(1, 1, 4096) == (8, 16)
        assert shape_buckets(9, 33, 4096) == (16, 64)
        assert shape_buckets(50, 4000, 4096) == (64, 4096)
        # chunk never exceeds the pool's pow2 ceiling
        assert shape_buckets(1, 9999, 4096) == (8, 4096)

    def test_descriptor_matches_static_executor(self):
        """Byte-identical pools vs the seed executor on random plans
        (loopback + overlapping ranges included)."""
        rng = np.random.default_rng(42)
        for trial in range(12):
            a, b = _fresh_transports(rng)
            for _ in range(3):
                plan = _random_plan(rng, int(rng.integers(1, 12)))
                a.execute_batch(plan)
                b.execute_batch_static(plan)
            np.testing.assert_array_equal(
                np.asarray(a.pool), np.asarray(b.pool),
                err_msg=f"divergence on trial {trial}")


WINDOW_CASES = ("row_end", "pool_below_chunk", "same_row_overlap",
                "cross_row", "zero_length", "bit_patterns")


def _window_case_rows(case, rng, pool):
    """One descriptor table's ``(src, dst, src_addr, dst_addr, length)``
    rows, drawn to put the windowed executor at one of its edges."""
    rows = []
    for i in range(int(rng.integers(1, 12))):
        ln = int(rng.integers(1, pool // 2))
        if case == "pool_below_chunk" and i == 0:
            ln = int(rng.integers(513, pool + 1))  # chunk 1024 > pool
        sa, da = (int(a) for a in rng.integers(0, pool - ln + 1, size=2))
        src, dst = (int(p) for p in rng.integers(0, 2, size=2))
        if case == "row_end":
            sa, da = (int(rng.choice([a, pool - ln])) for a in (sa, da))
        elif case == "same_row_overlap":
            dst = src
            da = int(np.clip(sa + rng.integers(-ln + 1, ln), 0, pool - ln))
        elif case == "cross_row":
            dst = 1 - src
        elif case == "zero_length" and rng.random() < 0.5:
            ln = 0
        rows.append((src, dst, sa, da, ln))
    return rows


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_window_executor_matches_numpy_replay(case):
    """The single-device executor moves each WQE through a chunk-sized
    window of the pool; on seeded random tables it must equal a numpy
    replay in table order, word for word. ``pool_below_chunk`` takes
    non-pow2 pools whose chunk bucket (pow2) exceeds the row, and
    ``bit_patterns`` fills the pool with arbitrary 32-bit words, NaN
    payloads included."""
    import jax.numpy as jnp
    from repro.core.rdma.transport import (_exec_descriptors_local,
                                           pack_descriptors)
    rng = np.random.default_rng(WINDOW_CASES.index(case))
    for trial in range(8):
        pool = (int(rng.integers(600, 1024)) if case == "pool_below_chunk"
                else int(rng.choice([1000, 1024])))
        rows = _window_case_rows(case, rng, pool)
        if case == "bit_patterns":
            init = rng.integers(0, 2 ** 32, size=(2, pool),
                                dtype=np.uint32).view(np.float32)
        else:
            init = rng.standard_normal((2, pool)).astype(np.float32)
        desc, chunk = pack_descriptors([("xfer", *r) for r in rows], pool)
        got = np.asarray(_exec_descriptors_local(jnp.asarray(init), desc,
                                                 chunk))
        want = init.copy()
        for src, dst, sa, da, ln in rows:
            want[dst, da:da + ln] = want[src, sa:sa + ln].copy()
        np.testing.assert_array_equal(
            got.view(np.uint32), want.view(np.uint32),
            err_msg=f"{case} trial {trial}: pool {pool} chunk {chunk} "
                    f"rows {rows}")


class TestCoalescer:
    def test_merges_contiguous_run(self):
        plan = [("xfer", 0, 1, i, 100 + i, 1) for i in range(50)]
        merged = coalesce_plan(plan)
        assert merged == [("xfer", 0, 1, 0, 100, 50)]

    def test_does_not_merge_direction_or_gap_changes(self):
        plan = [("xfer", 0, 1, 0, 100, 4),
                ("xfer", 1, 0, 4, 104, 4),    # direction flip
                ("xfer", 0, 1, 8, 108, 4),
                ("xfer", 0, 1, 13, 112, 4)]   # src gap
        assert len(coalesce_plan(plan)) == 4

    def test_loopback_overlap_not_merged(self):
        """On a loopback row, merging would change memcpy ordering when
        the combined ranges overlap — the guard must refuse."""
        plan = [("xfer", 0, 0, 0, 2, 4), ("xfer", 0, 0, 4, 6, 4)]
        assert len(coalesce_plan(plan)) == 2
        # disjoint loopback ranges do merge
        plan2 = [("xfer", 0, 0, 0, 32, 4), ("xfer", 0, 0, 4, 36, 4)]
        assert coalesce_plan(plan2) == [("xfer", 0, 0, 0, 32, 8)]

    def test_coalesced_semantics_equal_uncoalesced(self):
        """Random plans with contiguous runs: coalesced == original."""
        rng = np.random.default_rng(7)
        for _ in range(10):
            base = _random_plan(rng, int(rng.integers(1, 5)))
            # splice in a contiguous run
            sa, da = int(rng.integers(0, 16)), int(rng.integers(32, 48))
            run = [("xfer", 0, 1, sa + 2 * i, da + 2 * i, 2)
                   for i in range(4)]
            plan = base + run
            merged = coalesce_plan(plan)
            assert len(merged) <= len(plan)
            a, b = _fresh_transports(rng)
            a.execute_batch(plan)
            b.execute_batch(merged)
            np.testing.assert_array_equal(np.asarray(a.pool),
                                          np.asarray(b.pool))

    def test_engine_coalesces_contiguous_reads(self):
        eng = RDMAEngine(n_peers=2, pool_size=1024)
        qp = eng.create_qp(0, 1)
        eng.create_qp(1, 0)
        mr = eng.register_mr(1, 0, 512)
        eng.write_buffer(1, 0, np.arange(64, dtype=np.float32))
        for i in range(64):
            eng.post_send(qp, WQE(Opcode.READ, qp.qp_num, i,
                                  local_addr=512 + i, remote_addr=i,
                                  length=1, rkey=mr.rkey))
        eng.ring_sq_doorbell(qp)
        # 64 WQEs merged into ONE descriptor, still one dispatch
        assert eng.stats["coalesced_wqes"] == 63
        assert eng.stats["transport"]["coalesced_wqes"] == 63
        assert eng.transport.stats["wqes"] == 1
        assert eng.stats["wqes"] == 64          # verb-level count unchanged
        assert len(eng.poll_cq(qp, 64)) == 64   # every WQE completes
        np.testing.assert_array_equal(
            eng.read_buffer(0, 512, 64), np.arange(64, dtype=np.float32))


class TestCompletionPaths:
    def test_queue_pair_deque_window(self):
        """SQ holds only the unretired window; pending()/retire() are
        consistent with producer/doorbell/consumer indices."""
        qp = QueuePair(99, 0, 1)
        for i in range(6):
            qp.post_send(WQE(Opcode.WRITE, 99, i))
        qp.sq_doorbell = 4                       # doorbell covers 4 of 6
        pend = qp.pending()
        assert [w.wr_id for w in pend] == [0, 1, 2, 3]
        qp.retire(len(pend))
        assert qp.sq_cidx == 4 and len(qp.sq) == 2
        qp.sq_doorbell = 6
        assert [w.wr_id for w in qp.pending()] == [4, 5]

    def test_poll_cq_fifo_partial_drain(self):
        eng = RDMAEngine(n_peers=2, pool_size=512)
        qp = eng.create_qp(0, 1)
        eng.create_qp(1, 0)
        mr = eng.register_mr(1, 0, 256)
        for i in range(10):
            eng.post_send(qp, WQE(Opcode.READ, qp.qp_num, i,
                                  local_addr=256 + i, remote_addr=i,
                                  length=1, rkey=mr.rkey))
        eng.ring_sq_doorbell(qp)
        first = eng.poll_cq(qp, max_entries=3)
        rest = eng.poll_cq(qp, max_entries=64)
        assert [c.wr_id for c in first] == [0, 1, 2]
        assert [c.wr_id for c in rest] == list(range(3, 10))
        assert eng.poll_cq(qp) == []

    def test_recv_queue_fifo(self):
        eng = RDMAEngine(n_peers=2, pool_size=512)
        qp = eng.create_qp(0, 1)
        rqp = eng.create_qp(1, 0)
        eng.write_buffer(0, 0, np.arange(8, dtype=np.float32))
        for i in range(2):
            eng.post_recv(rqp, WQE(Opcode.RECV, rqp.qp_num, 100 + i,
                                   local_addr=64 + 16 * i, length=4))
        for i in range(2):
            eng.post_send(qp, WQE(Opcode.SEND, qp.qp_num, i,
                                  local_addr=4 * i, length=4))
        eng.ring_sq_doorbell(qp)
        rcqes = eng.poll_cq(rqp)
        assert [c.wr_id for c in rcqes] == [100, 101]  # RECVs in order
        np.testing.assert_array_equal(eng.read_buffer(1, 64, 4),
                                      [0, 1, 2, 3])
        np.testing.assert_array_equal(eng.read_buffer(1, 80, 4),
                                      [4, 5, 6, 7])


class TestResponderIndex:
    def test_matches_linear_scan_reference(self):
        eng = RDMAEngine(n_peers=4, pool_size=256)
        qps = [eng.create_qp(a, b) for a in range(4) for b in range(4)]
        qps += [eng.create_qp(0, 1), eng.create_qp(1, 0)]  # duplicates

        def reference(qp):
            for other in eng.qps.values():
                if (other.local_peer == qp.remote_peer
                        and other.remote_peer == qp.local_peer
                        and other.qp_num != qp.qp_num):
                    return other
            return None

        for qp in qps:
            assert eng._responder_qp(qp) is reference(qp)

    def test_loopback_qp_excludes_itself(self):
        eng = RDMAEngine(n_peers=2, pool_size=256)
        qp = eng.create_qp(0, 0)
        assert eng._responder_qp(qp) is None
        qp2 = eng.create_qp(0, 0)
        assert eng._responder_qp(qp) is qp2


def test_predict_from_stats_batching_wins():
    """The executed-stats bridge reproduces the paper's economics: one
    doorbell covering n WQEs beats n single-WQE doorbells."""
    from repro.core.rdma.simulator import predict_from_stats
    batched = predict_from_stats(
        {"dispatches": 1, "wqes": 50, "compiles": 1}, payload=4096)
    single = predict_from_stats(
        {"dispatches": 50, "wqes": 50, "compiles": 1}, payload=4096)
    assert batched["hw_predicted_s"] < single["hw_predicted_s"]
    assert batched["executor_predicted_s"] < single["executor_predicted_s"]
    assert batched["wqes_per_doorbell"] == 50.0


@pytest.mark.slow
def test_ici_transport_parity_and_cache(tmp_path):
    """ICITransport (forced 4-device mesh) matches LocalTransport byte
    for byte on an address-varying workload and reuses one compile."""
    code = """
import numpy as np
import jax.numpy as jnp
from repro.core.rdma.transport import (ICITransport, LocalTransport,
                                       make_transport)
rng = np.random.default_rng(0)
init = rng.standard_normal((4, 64)).astype(np.float32)
ici = make_transport(4, 64)
assert isinstance(ici, ICITransport), type(ici)
loc = LocalTransport(jnp.asarray(init))
ici.pool = jnp.asarray(init)
for _ in range(10):
    plan = []
    for _ in range(int(rng.integers(1, 6))):
        ln = int(rng.integers(1, 9))
        plan.append(("xfer", int(rng.integers(0, 4)), int(rng.integers(0, 4)),
                     int(rng.integers(0, 64 - ln)),
                     int(rng.integers(0, 64 - ln)), ln))
    ici.execute_batch(plan)
    loc.execute_batch(plan)
np.testing.assert_array_equal(np.asarray(ici.pool), np.asarray(loc.pool))
assert ici.stats["dispatches"] == 10
assert ici.stats["compiles"] <= 3, ici.stats   # few shape buckets only
print("ICI_PARITY_OK", ici.stats["compiles"])
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert "ICI_PARITY_OK" in r.stdout, r.stdout + r.stderr
