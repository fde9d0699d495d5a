"""Transport conformance suite: property-based contracts for the
multi-QP doorbell scheduler (`schedule_plan`), the coalescer, and the
descriptor-ized QDMA staging path.

The contracts:

* scheduling is a *permutation* that preserves each QP's posting order
  (prefix picks), honors the flush budget, and — under round-robin with
  equal weights — never lets one backlogged QP starve another;
* executing a scheduled (interleaved) plan through the descriptor
  executor is byte-identical to the seed static executor on the same
  order, for random QP mixes including overlapping address ranges;
* CQE order within each QP equals posting order, whatever the scheduler
  interleaves between QPs;
* `host_write`/`sync_host_to_dev` with varying data lengths stay inside
  the pow2 chunk-bucket compile budget and round-trip byte-identically
  through `host_read` on both transports.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.rdma import Opcode, RDMAEngine, WQE, schedule_plan
from repro.core.rdma.doorbell import coalesce_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

POOL = 64
N_PEERS = 2

# One transfer op: (src, dst, src_addr, dst_addr, length) over a small
# pool, so overlapping source/destination ranges are common.
_op = st.tuples(st.integers(0, N_PEERS - 1), st.integers(0, N_PEERS - 1),
                st.integers(0, POOL - 9), st.integers(0, POOL - 9),
                st.integers(1, 8))
_window = st.lists(_op, min_size=0, max_size=8)
_windows = st.lists(_window, min_size=1, max_size=5)
_scheduler = st.sampled_from(["rr", "fifo"])


def _entries(ops):
    return [("xfer", s, d, sa, da, ln) for (s, d, sa, da, ln) in ops]


def _transport_pair(seed):
    import jax.numpy as jnp
    from repro.core.rdma.transport import make_transport
    rng = np.random.default_rng(seed)
    init = rng.standard_normal((N_PEERS, POOL)).astype(np.float32)
    a = make_transport(N_PEERS, POOL)
    b = make_transport(N_PEERS, POOL)
    a.pool = jnp.asarray(init)
    b.pool = jnp.asarray(init)
    return a, b


class TestSchedulePlanContract:
    @settings(max_examples=60, deadline=None)
    @given(windows=_windows, scheduler=_scheduler,
           budget=st.integers(0, 30), use_budget=st.booleans())
    def test_prefix_permutation_and_budget(self, windows, scheduler,
                                           budget, use_budget):
        wins = [(i, ops) for i, ops in enumerate(windows)]
        merged, counts = schedule_plan(
            wins, scheduler=scheduler,
            budget=budget if use_budget else None)
        total = sum(len(w) for w in windows)
        cap = min(budget, total) if use_budget else total
        assert len(merged) == sum(counts.values()) == cap
        for qid, ops in wins:
            picks = [e for q, e in merged if q == qid]
            # prefix of the window, in posting order
            assert picks == list(ops[:counts[qid]])

    @settings(max_examples=60, deadline=None)
    @given(windows=_windows)
    def test_fifo_without_budget_is_concatenation(self, windows):
        wins = [(i, ops) for i, ops in enumerate(windows)]
        merged, _ = schedule_plan(wins, scheduler="fifo")
        assert merged == [(i, e) for i, ops in wins for e in ops]

    @settings(max_examples=60, deadline=None)
    @given(depths=st.lists(st.integers(1, 32), min_size=2, max_size=6),
           budget=st.integers(2, 24))
    def test_rr_no_starvation_with_equal_weights(self, depths, budget):
        """Every QP deep enough to use its fair share gets at least the
        floor of it — one deep SQ cannot starve the others."""
        wins = [(i, tuple(range(d))) for i, d in enumerate(depths)]
        _, counts = schedule_plan(wins, scheduler="rr", budget=budget)
        fair = budget // len(depths)
        for i, d in enumerate(depths):
            assert counts[i] >= min(d, fair)

    @settings(max_examples=40, deadline=None)
    @given(depths=st.lists(st.integers(8, 32), min_size=2, max_size=4),
           weights=st.lists(st.integers(1, 4), min_size=4, max_size=4))
    def test_weighted_rr_tracks_weights(self, depths, weights):
        """With all windows backlogged, one full budget round splits in
        weight proportion (each QP serves `weight` per cycle)."""
        weights = weights[:len(depths)]
        wsum = sum(weights)
        wins = [(i, tuple(range(d))) for i, d in enumerate(depths)]
        _, counts = schedule_plan(
            wins, scheduler="rr",
            weights={i: w for i, w in enumerate(weights)}, budget=wsum)
        # depths >= 8 >= max weight sum per cycle, so nothing runs dry
        assert [counts[i] for i in range(len(depths))] == weights


class TestDRRConformance:
    """Deficit round-robin with quantum carry-over: conservation, exact
    long-run proportional share, and the fifo age-promotion bound."""

    @settings(max_examples=30, deadline=None)
    @given(weights=st.lists(st.integers(1, 4), min_size=2, max_size=4),
           budget=st.integers(1, 16),
           depth_seed=st.integers(0, 10_000),
           flushes=st.integers(2, 20))
    def test_quantum_conservation_deficits_never_minted(
            self, weights, budget, depth_seed, flushes):
        """Across any flush sequence with ragged (even empty) windows:
        quanta credited == served + live deficit + credit destroyed on
        window drain, exactly, per QP. Deficits are never negative and
        never appear out of thin air."""
        import random
        rng = random.Random(depth_seed)
        n = len(weights)
        wmap = {i: w for i, w in enumerate(weights)}
        state = {}
        served = {i: 0 for i in range(n)}
        for _ in range(flushes):
            wins = [(i, tuple(range(rng.randint(0, 12)))) for i in range(n)]
            _, counts = schedule_plan(wins, scheduler="drr", weights=wmap,
                                      budget=budget, state=state)
            for i in range(n):
                served[i] += counts.get(i, 0)
            for i in range(n):
                credited = state["credited"].get(i, 0)
                deficit = state["deficits"].get(i, 0)
                destroyed = state["destroyed"].get(i, 0)
                assert deficit >= 0
                assert credited == served[i] + deficit + destroyed, (
                    i, credited, served[i], deficit, destroyed)

    @settings(max_examples=20, deadline=None)
    @given(weights=st.lists(st.integers(1, 5), min_size=2, max_size=5),
           budget=st.integers(2, 12),
           ragged_seed=st.integers(0, 10_000))
    def test_drr_long_run_share_proportional_to_weight(
            self, weights, budget, ragged_seed):
        """Continuously backlogged QPs with ragged window depths: over
        many budgeted flushes each QP's service share matches its weight
        within 5% (the acceptance criterion) — plain WRR drifts here
        because service a budget truncates mid-round is never repaid."""
        import random
        rng = random.Random(ragged_seed)
        n = len(weights)
        wmap = {i: w for i, w in enumerate(weights)}
        state = {}
        served = {i: 0 for i in range(n)}
        flushes = 150
        for _ in range(flushes):
            # ragged but never dry: depth >= budget keeps every QP
            # backlogged through the whole flush
            wins = [(i, tuple(range(budget + rng.randint(0, 7))))
                    for i in range(n)]
            _, counts = schedule_plan(wins, scheduler="drr", weights=wmap,
                                      budget=budget, state=state)
            for i in range(n):
                served[i] += counts.get(i, 0)
        total = sum(served.values())
        assert total == flushes * budget
        wsum = sum(weights)
        for i, w in enumerate(weights):
            assert abs(served[i] / total - w / wsum) <= 0.05, (
                weights, budget, served)

    @settings(max_examples=20, deadline=None)
    @given(n_victims=st.integers(1, 3), budget=st.integers(2, 8),
           promote_after=st.integers(1, 4))
    def test_fifo_age_promotion_no_starvation_bound(
            self, n_victims, budget, promote_after):
        """fifo with promote_after=T: a continuously backlogged QP is
        never unserved for more than T + ceil(victims/budget) consecutive
        flushes (T to get promoted, then the oldest-first promotion queue
        drains at `budget` QPs per flush) — the unbounded starvation fifo
        exhibits without promotion becomes a hard bound."""
        state = {}
        n = 1 + n_victims
        bound = promote_after + -(-n_victims // budget)
        gap = {i: 0 for i in range(n)}
        for _ in range(40):
            # QP0's window always deeper than the budget: unpromoted fifo
            # would hand it every flush forever
            wins = [(0, tuple(range(4 * budget)))]
            wins += [(i, tuple(range(4))) for i in range(1, n)]
            _, counts = schedule_plan(wins, scheduler="fifo", budget=budget,
                                      state=state,
                                      promote_after=promote_after)
            for i in range(n):
                gap[i] = 0 if counts.get(i, 0) else gap[i] + 1
                assert gap[i] <= bound, (i, gap, counts)

    def test_fifo_without_promotion_still_starves(self):
        """The baseline stays intact: no promote_after -> the deep first
        window takes every budget (the PR-2 starvation parity case)."""
        state = {}
        for _ in range(10):
            wins = [(0, tuple(range(64))), (1, tuple(range(8)))]
            _, counts = schedule_plan(wins, scheduler="fifo", budget=8,
                                      state=state)
            assert counts == {0: 8, 1: 0}

    def test_drr_engine_integration_shares_track_weights(self):
        """The engine-level acceptance check: RDMAEngine(scheduler='drr')
        under budgeted flushes serves re-armed windows in exact weight
        proportion over the long run, and the per-QP latency histogram
        ledger accounts every serviced WQE."""
        eng = RDMAEngine(n_peers=2, pool_size=4096, scheduler="drr",
                         flush_budget=8)
        mr = eng.register_mr(1, 0, 512)
        weights = [3, 2, 1]
        qps = [eng.create_qp(0, 1, weight=w) for w in weights]
        flushes = 60
        for _ in range(flushes):
            for q, qp in enumerate(qps):     # keep everyone backlogged
                while qp.pending_count < 8:
                    eng.post_send(qp, WQE(
                        Opcode.READ, qp.qp_num, wr_id=0,
                        local_addr=600 + q, remote_addr=q, length=1,
                        rkey=mr.rkey))
                    eng.ring_sq_doorbell(qp, defer=True)
            eng.flush_doorbells()
        service = eng.stats["qp_service"]
        total = sum(service[qp.qp_num] for qp in qps)
        for qp, w in zip(qps, weights):
            assert abs(service[qp.qp_num] / total - w / 6) <= 0.05, service
            assert (sum(eng.stats["qp_latency_us"][qp.qp_num].values())
                    == service[qp.qp_num])

    def test_drr_exact_share_when_weight_exceeds_flush_budget(self):
        """Regression: the engine snapshots at most flush_budget WQEs per
        QP, which drr must not mistake for a drained window — a weight
        LARGER than the budget spans several flushes and its cut quantum
        must be repaid, not destroyed. Weights {20,1}, budget 4: the
        long-run share is exactly 20/21, and no credit is ever destroyed
        while both QPs stay backlogged."""
        eng = RDMAEngine(n_peers=2, pool_size=4096, scheduler="drr",
                         flush_budget=4)
        mr = eng.register_mr(1, 0, 512)
        qps = [eng.create_qp(0, 1, weight=20), eng.create_qp(0, 1)]
        for _ in range(300):
            for q, qp in enumerate(qps):
                while qp.pending_count < 8:    # backlogged, ragged refill
                    eng.post_send(qp, WQE(
                        Opcode.READ, qp.qp_num, wr_id=0,
                        local_addr=600 + q, remote_addr=q, length=1,
                        rkey=mr.rkey))
                    eng.ring_sq_doorbell(qp, defer=True)
            eng.flush_doorbells()
        service = eng.stats["qp_service"]
        total = sum(service[qp.qp_num] for qp in qps)
        share = service[qps[0].qp_num] / total
        assert abs(share - 20 / 21) <= 0.05, service
        assert not eng._sched_state.get("destroyed"), eng._sched_state


class TestScheduledExecutionParity:
    @settings(max_examples=12, deadline=None)
    @given(windows=_windows, scheduler=_scheduler,
           budget=st.integers(1, 20), seed=st.integers(0, 999))
    def test_descriptor_matches_static_on_scheduled_order(
            self, windows, scheduler, budget, seed):
        """Random QP mixes with overlapping ranges: the interleaved plan
        must execute byte-identically on both executors."""
        wins = [(i, _entries(ops)) for i, ops in enumerate(windows)]
        merged, _ = schedule_plan(wins, scheduler=scheduler, budget=budget)
        plan = [e for _, e in merged]
        a, b = _transport_pair(seed)
        a.execute_batch(plan)
        b.execute_batch_static(plan)
        np.testing.assert_array_equal(np.asarray(a.pool),
                                      np.asarray(b.pool))

    @settings(max_examples=12, deadline=None)
    @given(windows=_windows, seed=st.integers(0, 999))
    def test_coalesced_schedule_matches_uncoalesced(self, windows, seed):
        """coalesce_plan over a scheduled order never changes semantics
        (overlap guard included) — on either executor."""
        wins = [(i, _entries(ops)) for i, ops in enumerate(windows)]
        merged, _ = schedule_plan(wins, scheduler="rr")
        plan = [e for _, e in merged]
        a, b = _transport_pair(seed)
        a.execute_batch(coalesce_plan(plan))
        b.execute_batch_static(plan)
        np.testing.assert_array_equal(np.asarray(a.pool),
                                      np.asarray(b.pool))


class TestEngineCQEOrdering:
    @settings(max_examples=10, deadline=None)
    @given(depths=st.lists(st.integers(1, 10), min_size=1, max_size=4),
           scheduler=_scheduler, budget=st.integers(1, 8),
           weights=st.lists(st.integers(1, 3), min_size=4, max_size=4))
    def test_per_qp_cqe_order_is_posting_order(self, depths, scheduler,
                                               budget, weights):
        """Concurrent deferred doorbells, budgeted flushes: every WQE
        completes exactly once and each QP's CQEs land in posting order."""
        eng = RDMAEngine(n_peers=2, pool_size=1024, scheduler=scheduler,
                         flush_budget=budget)
        mr = eng.register_mr(1, 0, 512)
        eng.write_buffer(1, 0, np.arange(512, dtype=np.float32))
        qps = [eng.create_qp(0, 1, weight=w)
               for w in weights[:len(depths)]]
        for q, (qp, depth) in enumerate(zip(qps, depths)):
            for i in range(depth):
                eng.post_send(qp, WQE(
                    Opcode.READ, qp.qp_num, wr_id=1000 * q + i,
                    local_addr=600 + 16 * q + i, remote_addr=16 * q + i,
                    length=1, rkey=mr.rkey))
            eng.ring_sq_doorbell(qp, defer=True)
        first = eng.flush_doorbells()
        # rr with budget >= one full round serves every backlogged QP
        if scheduler == "rr" and budget >= sum(qp.weight for qp in qps):
            assert all(first.get(qp.qp_num, 0) > 0 for qp in qps)
        for _ in range(200):
            if not any(qp.pending() for qp in qps):
                break
            eng.flush_doorbells()
        assert not any(qp.pending() for qp in qps)
        for q, (qp, depth) in enumerate(zip(qps, depths)):
            wr_ids = [c.wr_id for c in eng.poll_cq(qp, 256)]
            assert wr_ids == [1000 * q + i for i in range(depth)]

    def test_rr_shares_within_2x_of_even_fifo_starves(self):
        """The acceptance-criterion scenario: 4 QPs, one 8x deeper.
        RR keeps every backlogged QP's first-flush share within 2x of
        even; FIFO gives the deep QP the whole budget."""
        depths, budget = [32, 4, 4, 4], 16
        shares = {}
        for scheduler in ("rr", "fifo"):
            eng = RDMAEngine(n_peers=2, pool_size=1024,
                             scheduler=scheduler, flush_budget=budget)
            mr = eng.register_mr(1, 0, 512)
            qps = [eng.create_qp(0, 1) for _ in depths]
            for q, (qp, depth) in enumerate(zip(qps, depths)):
                for i in range(depth):
                    eng.post_send(qp, WQE(
                        Opcode.READ, qp.qp_num, wr_id=i,
                        local_addr=600 + q, remote_addr=q, length=1,
                        rkey=mr.rkey))
                eng.ring_sq_doorbell(qp, defer=True)
            counts = eng.flush_doorbells()
            shares[scheduler] = [counts.get(qp.qp_num, 0) for qp in qps]
        even = 16 / 4
        assert all(even / 2 <= c <= even * 2 for c in shares["rr"])
        assert shares["fifo"] == [16, 0, 0, 0]


class TestQDMAStaging:
    # 7 distinct lengths spanning exactly two pow2 chunk buckets
    LENGTHS = [17, 20, 25, 31, 70, 100, 127]

    def test_seven_lengths_at_most_two_compiles_roundtrip(self):
        from repro.core.rdma.transport import make_transport
        t = make_transport(2, 256)
        for i, ln in enumerate(self.LENGTHS):
            data = np.arange(ln, dtype=np.float32) + 10 * i
            t.host_write(i % 2, 2 * i, data)
            np.testing.assert_array_equal(t.host_read(i % 2, 2 * i, ln),
                                          data)
        assert t.stats["qdma_compiles"] <= 2, t.stats
        assert t.stats["qdma_cache_misses"] <= 2
        assert t.stats["qdma_writes"] == len(self.LENGTHS)
        assert (t.stats["qdma_cache_hits"]
                == len(self.LENGTHS) - t.stats["qdma_cache_misses"])

    def test_staged_matches_static_host_write(self):
        """Descriptor-ized QDMA == the seed per-length path, including
        overwrites at unaligned offsets."""
        import jax.numpy as jnp
        from repro.core.rdma.transport import make_transport
        rng = np.random.default_rng(3)
        init = rng.standard_normal((2, 256)).astype(np.float32)
        a = make_transport(2, 256)
        b = make_transport(2, 256)
        a.pool = jnp.asarray(init)
        b.pool = jnp.asarray(init)
        for _ in range(25):
            ln = int(rng.integers(1, 120))
            peer = int(rng.integers(0, 2))
            addr = int(rng.integers(0, 256 - ln))
            data = rng.standard_normal(ln).astype(np.float32)
            a.host_write(peer, addr, data)
            b.host_write_static(peer, addr, data)
        np.testing.assert_array_equal(np.asarray(a.pool),
                                      np.asarray(b.pool))

    def test_overrunning_host_write_raises(self):
        """The staging layer rejects pool-overrunning writes outright —
        the seed path would clamp-and-shift, the scatter path would drop
        lanes; both silently corrupt, so neither is allowed in."""
        from repro.core.rdma.transport import make_transport
        t = make_transport(2, 64)
        with pytest.raises(ValueError, match="out of bounds"):
            t.host_write(0, 60, np.zeros(8, np.float32))
        with pytest.raises(ValueError, match="out of bounds"):
            t.host_write(0, -1, np.zeros(4, np.float32))
        assert t.stats["qdma_writes"] == 0    # nothing was accounted

    def test_sync_host_to_dev_uses_staging_buckets(self):
        eng = RDMAEngine(n_peers=2, pool_size=512)
        for i, ln in enumerate(self.LENGTHS):
            eng.host_mem[0][i:i + ln] = np.arange(ln, dtype=np.float32)
            eng.sync_host_to_dev(0, i, ln)
            np.testing.assert_array_equal(
                eng.read_buffer(0, i, ln), np.arange(ln, dtype=np.float32))
        assert eng.stats["transport"]["qdma_compiles"] <= 2

    @pytest.mark.slow
    def test_ici_transport_qdma_parity_and_cache(self):
        """ICITransport (forced 4-device mesh): staged host_write round-
        trips byte-identically and stays inside the chunk-bucket compile
        budget."""
        code = """
import numpy as np
import jax.numpy as jnp
from repro.core.rdma.transport import ICITransport, make_transport
ici = make_transport(4, 256)
assert isinstance(ici, ICITransport), type(ici)
lengths = [17, 20, 25, 31, 70, 100, 127]
for i, ln in enumerate(lengths):
    data = np.arange(ln, dtype=np.float32) + i
    ici.host_write(i % 4, i, data)
    np.testing.assert_array_equal(ici.host_read(i % 4, i, ln), data)
assert ici.stats["qdma_compiles"] <= 2, ici.stats
assert ici.stats["qdma_writes"] == len(lengths)
print("ICI_QDMA_OK", ici.stats["qdma_compiles"])
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=560)
        assert "ICI_QDMA_OK" in r.stdout, r.stdout + r.stderr


class TestLossyFabricConformance:
    """Reliability-layer contract: any seeded fault profile that
    eventually delivers (loss rates bounded well under the retry budget)
    yields final buffer pools BYTE-IDENTICAL to the fault-free run, and
    per-QP CQE order equal to posting order. The workload gives each QP
    a disjoint destination region, so cross-QP commit reordering (DELAY
    faults) cannot mask a real divergence."""

    REGION = 512

    def _run(self, n_qps, depth, seed, injector=None):
        from repro.core.rdma import ReliabilityConfig
        pool = 4096
        eng = RDMAEngine(n_peers=2, pool_size=pool)
        if injector is not None:
            eng.install_fault_injector(
                injector, ReliabilityConfig(retry_cnt=16))
        eng.flush_budget = 8
        eng.scheduler = "drr"
        rng = np.random.default_rng(seed)
        init = rng.standard_normal(pool).astype(np.float32)
        eng.write_buffer(0, 0, init)
        qps, posted = [], {}
        for q in range(n_qps):
            qp = eng.create_qp(0, 1)
            mr = eng.register_mr(1, q * self.REGION, self.REGION)
            qps.append((qp, mr))
            posted[q] = []      # keyed by position: qp_nums are global
        for i in range(depth):
            for q, (qp, mr) in enumerate(qps):
                ln = int(rng.integers(1, 48))
                src = int(rng.integers(0, pool - ln))
                dst = q * self.REGION + int(rng.integers(
                    0, self.REGION - ln))
                wr = i * n_qps + q
                eng.post_send(qp, WQE(Opcode.WRITE, qp.qp_num, wr_id=wr,
                                      local_addr=src, remote_addr=dst,
                                      length=ln, rkey=mr.rkey))
                posted[q].append(wr)
        for qp, _ in qps:
            eng.ring_sq_doorbell(qp, defer=True)
        polled = {q: [] for q in range(n_qps)}
        for _ in range(600):
            eng.flush_doorbells()
            for q, (qp, _) in enumerate(qps):
                polled[q].extend(eng.poll_cq(qp))
            relia = eng._reliability
            if not any(qp.pending_count for qp, _ in qps) and (
                    relia is None or relia.outstanding() == 0):
                break
        return eng, posted, polled

    @settings(max_examples=8, deadline=None)
    @given(n_qps=st.integers(2, 4), depth=st.integers(4, 16),
           fault_seed=st.integers(0, 1 << 16),
           drop=st.floats(0.0, 0.12), duplicate=st.floats(0.0, 0.04),
           delay=st.floats(0.0, 0.03), corrupt=st.floats(0.0, 0.01))
    def test_seeded_faults_preserve_bytes_and_cqe_order(
            self, n_qps, depth, fault_seed, drop, duplicate, delay,
            corrupt):
        from repro.core.rdma import FaultInjector
        clean, posted, _ = self._run(n_qps, depth, seed=11)
        inj = FaultInjector(fault_seed, drop=drop, duplicate=duplicate,
                            delay=delay, corrupt=corrupt)
        faulted, posted2, polled = self._run(n_qps, depth, seed=11,
                                             injector=inj)
        assert posted == posted2
        for q, wrs in posted.items():
            cqes = polled[q]
            assert all(c.status.value == "success" for c in cqes)
            assert [c.wr_id for c in cqes] == wrs
        np.testing.assert_array_equal(
            np.asarray(faulted.transport.pool),
            np.asarray(clean.transport.pool))

    def test_ten_percent_drop_parity_and_full_ledger(self):
        """The ISSUE's acceptance point: 10% drop, byte parity, every
        CQE a SUCCESS, and the ledger accounts for the loss."""
        from repro.core.rdma import FaultInjector
        clean, posted, _ = self._run(3, 24, seed=42)
        inj = FaultInjector(42, drop=0.10, duplicate=0.05, delay=0.05,
                            corrupt=0.03)
        faulted, _, polled = self._run(3, 24, seed=42, injector=inj)
        np.testing.assert_array_equal(
            np.asarray(faulted.transport.pool),
            np.asarray(clean.transport.pool))
        for q, wrs in posted.items():
            assert [c.wr_id for c in polled[q]] == wrs
        rel = faulted.stats["reliability"]
        assert rel["acks"] == rel["psn_assigned"] == 3 * 24
        assert rel["retransmits"] >= rel["dropped"] > 0
        assert rel["retx_pressure"] == 0      # nothing left outstanding

    @pytest.mark.slow
    def test_ici_transport_parity_under_faults(self):
        """Same contract on the real ICITransport (forced 4-device host
        mesh): 10% seeded drop + dup + corrupt, byte parity with the
        fault-free run, zero outstanding retransmits at the end."""
        code = """
import numpy as np
from repro.core.rdma import (FaultInjector, Opcode, RDMAEngine,
                             ReliabilityConfig, WQE)
from repro.core.rdma.transport import ICITransport

def run(injector=None):
    eng = RDMAEngine(n_peers=4, pool_size=1024)
    assert isinstance(eng.transport, ICITransport), type(eng.transport)
    if injector is not None:
        eng.install_fault_injector(injector, ReliabilityConfig())
    eng.flush_budget = 6
    rng = np.random.default_rng(11)
    eng.write_buffer(0, 0, rng.standard_normal(1024).astype(np.float32))
    qps = []
    for q in range(2):
        qp = eng.create_qp(0, q + 1)
        mr = eng.register_mr(q + 1, 0, 512)
        qps.append(qp)
        for i in range(10):
            ln = int(rng.integers(1, 32))
            eng.post_send(qp, WQE(Opcode.WRITE, qp.qp_num,
                                  wr_id=i, local_addr=int(
                                      rng.integers(0, 1024 - ln)),
                                  remote_addr=int(rng.integers(0, 512 - ln)),
                                  length=ln, rkey=mr.rkey))
        eng.ring_sq_doorbell(qp, defer=True)
    for _ in range(300):
        eng.flush_doorbells()
        relia = eng._reliability
        if not any(qp.pending_count for qp in qps) and (
                relia is None or relia.outstanding() == 0):
            break
    return eng

clean = run()
faulted = run(FaultInjector(3, drop=0.10, duplicate=0.05, corrupt=0.03))
np.testing.assert_array_equal(np.asarray(faulted.transport.pool),
                              np.asarray(clean.transport.pool))
rel = faulted.stats["reliability"]
assert rel["retransmits"] > 0 and rel["retx_pressure"] == 0, rel
print("ICI_RELIABILITY_OK", rel["retransmits"])
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=560)
        assert "ICI_RELIABILITY_OK" in r.stdout, r.stdout + r.stderr
