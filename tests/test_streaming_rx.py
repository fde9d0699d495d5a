"""Streaming-compute RX ring conformance (paper §IV-D).

Contracts pinned here:

* ring mechanics — slot data lands in the device pool, full-ring pushes
  surface as counted drop/backpressure (policy-dependent, mirrored into
  ``transport.stats``), claimed slots stay allocated until their gather
  lands, wrap-around bursts split into two spans and preserve order;
* ``stream()`` parity — the RX-ring ``packet_parser`` is byte-identical
  to the ControlMsg path on the same packet set (LocalTransport here,
  ICITransport in a forced multi-device subprocess), serial AND
  pipelined, including meta-ring wrap;
* steady-state streaming adds ZERO new descriptor-program compiles after
  one warm-up cycle;
* pipelined invocations overlap: fewer flushes than serial, fetches and
  write-backs sharing descriptor tables (``lc_pipeline`` ledger), and
  head/tail credits conserved;
* the ``TrafficRouter.ingest_packets`` ingress lands exactly the
  non-RDMA share in the ring;
* kernel faults inside a generator kernel surface as
  ``StatusMsg(ok=False)`` in both service modes.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lookaside import ControlMsg, LookasideBlock
from repro.core.rdma import RDMAEngine
from repro.core.streaming import RXRing, TrafficRouter, make_roce_header
from repro.kernels import ref
from repro.kernels.lc_offload import (PARSER_WORKLOAD,
                                      STREAM_PARSER_WORKLOAD,
                                      register_default_kernels)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

RNG = np.random.default_rng(21)
POOL = 1 << 15
DATA_PEER, LC_PEER = 1, 0


def _headers(n):
    pkts = RNG.integers(0, 256, size=(n, 64)).astype(np.uint8)
    pkts[::2, 12:14] = [8, 0]
    pkts[::2, 23] = 17
    pkts[::2, 36:38] = [18, 183]
    return pkts


def _want(pkts):
    return np.asarray(ref.ref_parse_packets(jnp.asarray(pkts)))


def _stream_setup(depth=16, burst=8, pipeline_depth=1, policy="drop"):
    eng = RDMAEngine(n_peers=2, pool_size=POOL)
    blk = LookasideBlock(eng, peer=LC_PEER, scratch_base=POOL // 2,
                         scratch_size=POOL // 4,
                         pipeline_depth=pipeline_depth,
                         eager_writeback=(pipeline_depth == 1))
    register_default_kernels(blk)
    ring = RXRing(eng, peer=LC_PEER, base=POOL - depth * 64, depth=depth,
                  policy=policy)
    out_mr = eng.register_mr(DATA_PEER, 0, depth * 4)
    k = blk.attach_ring(STREAM_PARSER_WORKLOAD, ring, out_peer=DATA_PEER,
                        out_rkey=out_mr.rkey, out_base=0, burst=burst)
    return eng, blk, ring, k


def _meta_rows(eng, ring, seqs):
    rows = eng.read_buffer(DATA_PEER, 0, ring.depth * 4
                           ).reshape(ring.depth, 4)
    return np.stack([rows[s % ring.depth] for s in seqs])


class TestRingMechanics:
    def test_slot_data_lands_in_pool(self):
        eng, _, ring, _ = _stream_setup(depth=4)
        pkts = _headers(3)
        for h in pkts:
            assert ring.push(h)
        for i, h in enumerate(pkts):
            got = eng.read_buffer(LC_PEER, ring.slot_addr(i), 64)
            np.testing.assert_array_equal(got, h.astype(np.float32))
        assert ring.occupancy == 3 and ring.space == 1

    def test_full_ring_drop_policy_counts(self):
        eng, _, ring, _ = _stream_setup(depth=4, policy="drop")
        for h in _headers(4):
            assert ring.push(h)
        assert not ring.push(_headers(1)[0])
        assert ring.stats["dropped"] == 1
        assert eng.stats["transport"]["rx_ring_dropped"] == 1
        assert eng.stats["transport"]["rx_ring_pushed"] == 4
        assert eng.stats["transport"]["rx_ring_peak_occupancy"] == 4

    def test_full_ring_backpressure_policy_counts(self):
        eng, _, ring, k = _stream_setup(depth=4, policy="backpressure")
        pkts = _headers(5)
        for h in pkts[:4]:
            assert ring.push(h)
        assert not ring.push(pkts[4])
        assert ring.stats["backpressure"] == 1
        assert ring.stats["dropped"] == 0
        assert eng.stats["transport"]["rx_ring_backpressure"] == 1
        k.stream()                       # drain frees the ring
        assert ring.push(pkts[4])        # the refused packet retries

    def test_claimed_slots_stay_allocated_until_gather_lands(self):
        _, _, ring, _ = _stream_setup(depth=4)
        for h in _headers(4):
            ring.push(h)
        spans, stamps = ring.begin_consume(3)
        assert len(stamps) == 3
        assert ring.available == 1       # claimed slots not re-claimable
        assert ring.space == 0           # ...and not yet free for pushes
        assert not ring.push(_headers(1)[0])
        ring.complete_consume(3)
        assert ring.space == 3
        assert ring.push(_headers(1)[0])

    def test_wrap_around_splits_into_two_ordered_spans(self):
        _, _, ring, _ = _stream_setup(depth=8)
        for h in _headers(8):
            ring.push(h)
        ring.begin_consume(6)
        ring.complete_consume(6)         # head = 6
        for h in _headers(4):            # seq 8..11 -> slots 0..3
            assert ring.push(h)
        spans, _ = ring.begin_consume(6)  # seq 6..11 wraps at 8
        assert spans == [(ring.slot_addr(6), 2), (ring.base, 4)]
        assert ring.stats["wrap_bursts"] == 1


class TestStreamParity:
    def _controlmsg_meta(self, pkts):
        eng = RDMAEngine(n_peers=2, pool_size=POOL)
        blk = LookasideBlock(eng, peer=LC_PEER, scratch_base=POOL // 2,
                             scratch_size=POOL // 4)
        register_default_kernels(blk)
        n = len(pkts)
        mr = eng.register_mr(DATA_PEER, 0, n * 68)
        eng.write_buffer(DATA_PEER, 0, pkts.astype(np.float32).ravel())
        blk.dispatch(ControlMsg(
            PARSER_WORKLOAD, (DATA_PEER, mr.rkey, 0, n, n * 64), tag=1))
        assert blk.poll(PARSER_WORKLOAD).ok
        return eng.read_buffer(DATA_PEER, n * 64, n * 4).reshape(n, 4)

    @pytest.mark.parametrize("pipeline_depth", [1, 4])
    def test_stream_byte_identical_to_controlmsg_path(self,
                                                      pipeline_depth):
        pkts = _headers(14)
        eng, _, ring, k = _stream_setup(depth=16, burst=8,
                                        pipeline_depth=pipeline_depth)
        for h in pkts:
            assert ring.push(h)
        assert k.stream() == 14          # bursts of 8 + 6
        got = _meta_rows(eng, ring, range(14))
        np.testing.assert_array_equal(got, self._controlmsg_meta(pkts))
        np.testing.assert_array_equal(got, _want(pkts))

    def test_wrap_burst_meta_rows_land_at_matching_slots(self):
        """A burst split by the ring boundary writes its meta rows to
        the same (wrapped) slot indices, in arrival order."""
        pkts = _headers(20)
        eng, _, ring, k = _stream_setup(depth=16, burst=6)
        for h in pkts[:16]:
            ring.push(h)
        assert k.stream(max_bursts=1) == 6           # head=6
        for h in pkts[16:]:                          # seq 16..19 wrap
            assert ring.push(h)
        assert k.stream() == 14          # bursts 6..12, 12..18 (split), 18..20
        assert ring.stats["wrap_bursts"] == 1
        # seqs 16..19 re-used slots 0..3, so only the last depth seqs
        # are live in the meta ring — in arrival order, wrap included
        got = _meta_rows(eng, ring, range(4, 20))
        np.testing.assert_array_equal(got, _want(pkts)[4:])

    def test_zero_new_descriptor_compiles_after_warmup(self):
        from repro.core.rdma.transport import (descriptor_cache_size,
                                               staging_cache_size)
        pkts = _headers(64)
        for depth in (1, 4):
            eng, _, ring, k = _stream_setup(depth=16, burst=8,
                                            pipeline_depth=depth)

            def cycle():
                i = 0
                while i < len(pkts):
                    n = min(16, len(pkts) - i)
                    for h in pkts[i:i + n]:
                        assert ring.push(h)
                    assert k.stream() == n
                    i += n

            cycle()                      # warm every shape bucket
            d0, s0 = descriptor_cache_size(), staging_cache_size()
            cycle()                      # steady state: nothing compiles
            assert descriptor_cache_size() - d0 == 0
            assert staging_cache_size() - s0 == 0

    def test_pipelined_overlap_and_credit_conservation(self):
        pkts = _headers(48)
        # burst 6 -> 3 bursts per 16-packet cycle: one more than the
        # depth-4 block's fetch window, so round 2's fetch must overlap
        # round 1's write-backs
        eng_s, _, ring_s, k_s = _stream_setup(depth=16, burst=6,
                                              pipeline_depth=1)
        eng_p, _, ring_p, k_p = _stream_setup(depth=16, burst=6,
                                              pipeline_depth=4)
        for eng, ring, k in ((eng_s, ring_s, k_s), (eng_p, ring_p, k_p)):
            i = 0
            while i < len(pkts):
                for h in pkts[i:i + 16]:
                    ring.push(h)
                k.stream()
                i += 16
        np.testing.assert_array_equal(
            _meta_rows(eng_p, ring_p, range(32, 48)),
            _meta_rows(eng_s, ring_s, range(32, 48)))
        lp = eng_p.stats["lc_pipeline"]
        assert eng_p.stats["flushes"] < eng_s.stats["flushes"]
        assert lp["overlapped_flushes"] > 0
        assert lp["fetch_wqes_overlapped"] > 0
        assert lp["head"] == lp["tail"] == 9      # 3 bursts x 3 cycles
        assert 1 < lp["in_flight_peak"] <= lp["depth"]
        # every ring latency sample accounted at status time
        assert (sum(ring_p.stats["latency_us"].values())
                == ring_p.stats["consumed"] == 48)

    def test_second_block_shares_engine_pipeline_ledger(self):
        """Two blocks on one engine accumulate into the SAME lc_pipeline
        ledger (engine-wide, like qp_service) — constructing a second
        block must not zero the first block's history."""
        eng, blk, ring, k = _stream_setup(depth=8, burst=4,
                                          pipeline_depth=4)
        for h in _headers(8):
            ring.push(h)
        assert k.stream() == 8
        head0 = eng.stats["lc_pipeline"]["head"]
        assert head0 == 2
        blk2 = LookasideBlock(eng, peer=LC_PEER, scratch_base=0,
                              scratch_size=64, pipeline_depth=2)
        assert eng.stats["lc_pipeline"]["head"] == head0   # preserved
        assert eng.stats["lc_pipeline"]["depth"] == 4      # deepest wins
        assert blk2._lp is eng.stats["lc_pipeline"]

    def test_generator_kernel_fault_surfaces_not_ok_status(self):
        """A failing ring gather (bad rkey) must surface as
        StatusMsg(ok=False) through the generator phases — serial and
        pipelined."""
        for depth in (1, 4):
            eng, blk, ring, k = _stream_setup(depth=8, burst=4,
                                              pipeline_depth=depth)
            k.stream_out = (DATA_PEER, 0xBAD, 0)     # corrupt out rkey
            for h in _headers(4):
                ring.push(h)
            assert k.stream() == 4
            st = blk.poll(STREAM_PARSER_WORKLOAD)
            assert st is not None and not st.ok
            assert blk.stats["errors"] == 1
            # the failed invocation still released its claimed slots
            assert ring.space == ring.depth

    def test_fetch_phase_fault_still_frees_ring_slots(self):
        """A kernel that faults BEFORE its first yield (scratch
        exhaustion during the fetch phase) must still release the
        burst's claimed slots — otherwise the ring wedges with head
        stuck behind pend and every later push is refused."""
        for depth in (1, 4):
            eng, blk, ring, k = _stream_setup(depth=8, burst=8,
                                              pipeline_depth=depth)
            # shrink scratch so ctx.alloc raises before any WQE posts
            blk.scratch_size = 16
            blk._part_size = 16 // blk.pipeline_depth
            pkts = _headers(8)
            for h in pkts:
                assert ring.push(h)
            assert k.stream() == 8
            st = blk.poll(STREAM_PARSER_WORKLOAD)
            assert st is not None and not st.ok
            assert "scratch" in st.detail
            assert ring.space == ring.depth      # slots freed, no wedge
            assert ring.push(pkts[0])            # ring still usable

    @pytest.mark.slow
    def test_stream_parity_on_ici_transport(self):
        """RX-ring streaming on the real collective transport (forced
        2-device mesh): byte-identical to the ControlMsg path."""
        code = """
import numpy as np
import jax.numpy as jnp
from repro.core.lookaside import ControlMsg, LookasideBlock
from repro.core.rdma import RDMAEngine
from repro.core.rdma.transport import ICITransport
from repro.core.streaming import RXRing
from repro.kernels import ref
from repro.kernels.lc_offload import (STREAM_PARSER_WORKLOAD,
                                      register_default_kernels)

POOL = 1 << 15
rng = np.random.default_rng(3)
pkts = rng.integers(0, 256, size=(12, 64)).astype(np.uint8)
pkts[::2, 12:14] = [8, 0]; pkts[::2, 23] = 17; pkts[::2, 36:38] = [18, 183]

eng = RDMAEngine(n_peers=2, pool_size=POOL)
assert isinstance(eng.transport, ICITransport), type(eng.transport)
blk = LookasideBlock(eng, peer=0, scratch_base=POOL // 2,
                     scratch_size=POOL // 4, pipeline_depth=2,
                     eager_writeback=False)
register_default_kernels(blk)
ring = RXRing(eng, peer=0, base=POOL - 16 * 64, depth=16)
out_mr = eng.register_mr(1, 0, 64)
k = blk.attach_ring(STREAM_PARSER_WORKLOAD, ring, out_peer=1,
                    out_rkey=out_mr.rkey, out_base=0, burst=8)
for h in pkts:
    assert ring.push(h)
assert k.stream() == 12
got = eng.read_buffer(1, 0, 16 * 4).reshape(16, 4)[:12]
np.testing.assert_array_equal(
    got, np.asarray(ref.ref_parse_packets(jnp.asarray(pkts))))
print("ICI_STREAM_OK", eng.stats["lc_pipeline"]["head"])
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=560)
        assert "ICI_STREAM_OK" in r.stdout, r.stdout + r.stderr


class TestIngress:
    def test_router_lands_non_rdma_packets_in_ring(self):
        eng, blk, ring, k = _stream_setup(depth=8, burst=8)
        router = TrafficRouter(rx_ring=ring)
        headers = np.stack([make_roce_header(4, 7, is_rdma=(i % 2 == 0))
                            for i in range(8)])
        counts = router.ingest_packets(headers)
        assert counts == {"rdma": 4, "streamed": 4, "dropped": 0,
                          "backpressure": 0, "shed": 0}
        assert router.pkt_counters["streamed"] == 4
        assert ring.occupancy == 4
        assert k.stream() == 4           # only the non-RDMA share parses
        got = _meta_rows(eng, ring, range(4))
        want = _want(headers[1::2])
        np.testing.assert_array_equal(got, want)
        assert not got[:, 0].any()       # all non-RDMA rows

    def test_ingest_ring_full_outcome_matches_ring_policy(self):
        for policy, key in (("drop", "dropped"),
                            ("backpressure", "backpressure")):
            _, _, ring, _ = _stream_setup(depth=2, policy=policy)
            router = TrafficRouter(rx_ring=ring)
            headers = np.stack([make_roce_header(0, 1, is_rdma=False)
                                for _ in range(4)])
            counts = router.ingest_packets(headers)
            assert counts["streamed"] == 2 and counts[key] == 2, counts
            # router and ring telemetry must agree on the loss mode
            assert ring.stats[key] == 2
            assert router.pkt_counters[key] == 2

    def test_router_without_ring_drops_streamed_share(self):
        router = TrafficRouter()
        counts = router.ingest_packets(
            np.stack([make_roce_header(0, 1, is_rdma=False)]))
        assert counts == {"rdma": 0, "streamed": 0, "dropped": 1,
                          "backpressure": 0, "shed": 0}
