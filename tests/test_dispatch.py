"""Match→action dispatch plane conformance (the PR-5 tentpole).

Contracts pinned here:

* ``MatchTable`` semantics — priority wins, ties to the latest entry,
  ranges inclusive, unnamed fields wildcard, unknown fields raise, the
  default action catches everything else; vectorized ``classify`` agrees
  with scalar ``match``;
* the STRUCTURED Action API — ``Forward``/``Drop``/``Stream``/
  ``Handler``/``Chain`` actions with the shed flag folded in; legacy
  int/sentinel actions classify identically through the ``as_action``
  deprecation shim (one warning each) while no in-repo caller uses
  them;
* full-field classification — ``classify_headers`` returns the raw
  parsed vectors (opcode/dest_qp unmasked) so non-RDMA classes stay
  separable, consistent with the ``ref_parse_fields`` oracle and with
  the masked 4-column meta view;
* dispatch parity — a mixed-class stream (3 classes, 2 handlers) is
  routed ingress→ring→sub-bursts→kernels with every handler's rows
  byte-identical to its direct-invoke oracle (LocalTransport here,
  ICITransport in a forced multi-device subprocess), the per-round
  operand gathers of BOTH handlers sharing one flush;
* steady-state mixed-class streaming compiles ZERO new descriptor or
  staging programs after one warm-up cycle;
* wrap × multi-class interplay — sub-bursts straddling the ring wrap
  keep per-handler FIFO order, and drop-vs-backpressure accounting
  agrees between ``TrafficRouter.pkt_counters`` and the ring/transport
  ``rx_ring_*`` counters;
* bucket pre-warm — replaying a ``bucket_hist`` on a fresh transport
  leaves zero cold-start cache misses and does not touch the pool;
* rkey determinism — engines mint identical rkey sequences regardless
  of construction order; the module-global ``next_rkey`` shim is GONE.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lookaside import LookasideBlock
from repro.core.rdma import RDMAEngine
from repro.core.streaming import (Chain, Drop, Forward, Handler,
                                  MatchTable, RXRing, Stream,
                                  StreamDispatcher, TrafficRouter,
                                  as_action, classify_headers,
                                  make_roce_header)
from repro.kernels import ref
from repro.kernels.lc_offload import (QUANT_ROW, STREAM_PARSER_WORKLOAD,
                                      STREAM_QUANT_WORKLOAD,
                                      register_default_kernels)
from repro.kernels.packet_parser import FIELD_NAMES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

RNG = np.random.default_rng(5)
POOL = 1 << 15
DATA_PEER, LC_PEER = 1, 0
CTRL_PORT, BULK_PORT = 9000, 9100
META_BASE, QUANT_BASE = 0, 2048
F = {name: i for i, name in enumerate(FIELD_NAMES)}


def _ctrl_header(i=0):
    return make_roce_header(i % 18, i, is_rdma=False, dport=CTRL_PORT)


def _bulk_header(seed=0):
    # the classifier owns the header byte layout; randomize only the
    # payload tail so the quantizer sees varied bytes
    h = make_roce_header(seed % 18, seed, is_rdma=False, dport=BULK_PORT)
    h[50:] = RNG.integers(0, 256, 14).astype(np.uint8)
    return h


def _mixed_headers(n):
    """Interleaved rdma / ctrl / bulk stream (3 classes)."""
    out = []
    for i in range(n):
        kind = i % 3
        out.append(make_roce_header(4, i) if kind == 0
                   else _ctrl_header(i) if kind == 1 else _bulk_header())
    return np.stack(out)


def _table():
    return (MatchTable(default=Drop())
            .add(Forward(), priority=10, is_rdma=1)
            .add(Handler(STREAM_PARSER_WORKLOAD), udp_dport=CTRL_PORT)
            .add(Handler(STREAM_QUANT_WORKLOAD), udp_dport=BULK_PORT))


def _dispatch_setup(depth=16, burst=8, pipeline_depth=4, policy="drop"):
    eng = RDMAEngine(n_peers=2, pool_size=POOL)
    blk = LookasideBlock(eng, peer=LC_PEER, scratch_base=POOL // 2,
                         scratch_size=POOL // 4,
                         pipeline_depth=pipeline_depth,
                         eager_writeback=(pipeline_depth == 1))
    register_default_kernels(blk)
    ring = RXRing(eng, peer=LC_PEER, base=POOL - depth * 64, depth=depth,
                  policy=policy)
    meta_mr = eng.register_mr(DATA_PEER, META_BASE, depth * 4)
    quant_mr = eng.register_mr(DATA_PEER, QUANT_BASE, depth * QUANT_ROW)
    disp = StreamDispatcher(blk, ring, _table(), burst=burst)
    disp.register_handler(STREAM_PARSER_WORKLOAD, DATA_PEER,
                          meta_mr.rkey, META_BASE)
    disp.register_handler(STREAM_QUANT_WORKLOAD, DATA_PEER,
                          quant_mr.rkey, QUANT_BASE)
    router = TrafficRouter(rx_ring=ring, table=disp.table)
    return eng, blk, ring, disp, router


def _rows(eng, depth, seqs, base, width):
    rows = eng.read_buffer(DATA_PEER, base, depth * width
                           ).reshape(depth, width)
    return np.stack([rows[s % depth] for s in seqs])


def _want_quant(hdrs):
    q, s = ref.ref_quantize(jnp.asarray(np.asarray(hdrs, np.float32)))
    return np.concatenate([np.asarray(q, np.float32),
                           np.asarray(s, np.float32)], axis=1)


class TestMatchTable:
    def test_priority_and_tie_break(self):
        t = (MatchTable(default=Drop())
             .add(Handler(1), priority=1, udp_dport=80)
             .add(Handler(2), priority=9, udp_dport=80)
             .add(Handler(3), priority=9, udp_dport=80))
        vec = np.zeros(len(FIELD_NAMES), np.int64)
        vec[F["udp_dport"]] = 80
        assert t.match(vec) == Handler(3)     # priority, then latest
        vec[F["udp_dport"]] = 81
        assert t.match(vec) == Drop()         # default catches the rest

    def test_ranges_inclusive_and_wildcards(self):
        t = MatchTable(default=Drop()).add(Handler(7), opcode=(6, 11))
        for op, want in ((5, Drop()), (6, Handler(7)), (11, Handler(7)),
                         (12, Drop())):
            vec = np.zeros(len(FIELD_NAMES), np.int64)
            vec[F["opcode"]] = op
            assert t.match(vec) == want, op

    def test_multi_field_entries_are_conjunctions(self):
        t = MatchTable(default=Drop()).add(Forward(), is_rdma=1,
                                           opcode=(12, 12))
        vec = np.zeros(len(FIELD_NAMES), np.int64)
        vec[F["is_rdma"]], vec[F["opcode"]] = 1, 12
        assert t.match(vec) == Forward()
        vec[F["opcode"]] = 13
        assert t.match(vec) == Drop()

    def test_unknown_field_and_empty_range_raise(self):
        with pytest.raises(KeyError, match="unknown match field"):
            MatchTable().add(Forward(), not_a_field=3)
        with pytest.raises(ValueError, match="empty range"):
            MatchTable().add(Forward(), opcode=(5, 2))

    def test_classify_agrees_with_match(self):
        t = _table()
        hdrs = _mixed_headers(12)
        fields = classify_headers(hdrs)
        acts = t.classify(fields)
        assert acts == [t.match(v) for v in fields]
        assert acts[::3] == [Forward()] * 4
        assert acts[1::3] == [Handler(STREAM_PARSER_WORKLOAD)] * 4
        assert acts[2::3] == [Handler(STREAM_QUANT_WORKLOAD)] * 4

    def test_handler_ids_lists_handler_actions(self):
        assert _table().handler_ids == [STREAM_PARSER_WORKLOAD,
                                        STREAM_QUANT_WORKLOAD]


class TestActionAPI:
    def test_shed_folds_into_the_action(self):
        t = (MatchTable(default=Stream())
             .add(Forward(), is_rdma=1)
             .add(Stream(shed=True), udp_dport=80))
        vec = np.zeros(len(FIELD_NAMES), np.int64)
        vec[F["udp_dport"]] = 80
        assert t.match(vec).shed
        vec[F["udp_dport"]] = 81
        assert not t.match(vec).shed
        # the add(..., shed=True) spelling folds too, and never marks Drop
        t2 = MatchTable().add(Handler(5), shed=True, udp_dport=80)
        assert t2.entries[0].action == Handler(5, shed=True)
        assert as_action(Drop(), shed=True) == Drop()

    def test_chain_tag_deterministic_and_disjoint(self):
        c = Chain((0x22, 0x23), name="egress")
        assert c.tag == Chain((0x22, 0x23)).tag          # name-independent
        assert c.tag != Chain((0x23, 0x22)).tag          # order matters
        assert c.tag >> 24 == 0x43                       # disjoint from wids
        assert c.stages == (0x22, 0x23)
        with pytest.raises(ValueError):
            Chain(())

    def test_legacy_int_and_sentinel_actions_classify_identically(self):
        """The deprecation shim: a legacy int/sentinel table classifies
        EXACTLY like its structured twin, one warning per coercion."""
        with pytest.warns(DeprecationWarning) as rec:
            legacy = (MatchTable(default="drop")
                      .add("rdma", priority=10, is_rdma=1)
                      .add(STREAM_PARSER_WORKLOAD, udp_dport=CTRL_PORT)
                      .add(STREAM_QUANT_WORKLOAD, udp_dport=BULK_PORT))
        assert len(rec) == 3 + 1                         # 3 adds + default
        fields = classify_headers(_mixed_headers(12))
        assert legacy.classify(fields) == _table().classify(fields)
        assert legacy.handler_ids == _table().handler_ids

    def test_shim_rejects_unknown_actions(self):
        with pytest.raises(TypeError, match="unsupported table action"):
            as_action("tie")
        with pytest.raises(TypeError):
            as_action(True)                              # bool is not a wid
        assert as_action(Forward()) == Forward()         # passthrough


class TestFullFieldClassifier:
    def test_fields_match_oracle_and_meta_view(self):
        hdrs = _mixed_headers(9)
        fields = classify_headers(hdrs)
        want = np.asarray(ref.ref_parse_fields(jnp.asarray(hdrs)))
        np.testing.assert_array_equal(fields, want)
        meta = np.asarray(ref.ref_parse_packets(jnp.asarray(hdrs)))
        # masked meta view derives from the raw fields
        np.testing.assert_array_equal(meta[:, 0], fields[:, 0])
        np.testing.assert_array_equal(meta[:, 1],
                                      fields[:, 1] * fields[:, 0])
        np.testing.assert_array_equal(meta[:, 3], fields[:, 3])

    def test_non_rdma_ports_stay_separable(self):
        """The refactor's point: the old 4-column view zeroed everything
        that distinguishes non-RDMA classes."""
        fields = classify_headers(np.stack([_ctrl_header(),
                                            _bulk_header()]))
        assert fields[0, F["udp_dport"]] == CTRL_PORT
        assert fields[1, F["udp_dport"]] == BULK_PORT
        assert not fields[:, F["is_rdma"]].any()


class TestDispatchParity:
    @pytest.mark.parametrize("pipeline_depth", [1, 4])
    def test_mixed_stream_byte_identical_to_oracles(self, pipeline_depth):
        hdrs = _mixed_headers(24)
        eng, _, ring, disp, router = _dispatch_setup(
            depth=16, burst=4, pipeline_depth=pipeline_depth)
        counts = router.ingest_packets(hdrs)
        assert counts == {"rdma": 8, "streamed": 16, "dropped": 0,
                          "backpressure": 0, "shed": 0}
        assert disp.service() == 16
        # streamed slots alternate ctrl/bulk in arrival order: ctrl at
        # even seqs, bulk at odd seqs
        got_meta = _rows(eng, 16, range(0, 16, 2), META_BASE, 4)
        got_quant = _rows(eng, 16, range(1, 16, 2), QUANT_BASE, QUANT_ROW)
        np.testing.assert_array_equal(
            got_meta, np.asarray(ref.ref_parse_packets(
                jnp.asarray(hdrs[1::3]))))
        np.testing.assert_array_equal(got_quant, _want_quant(hdrs[2::3]))
        assert ring.space == ring.depth      # all slots freed

    def test_handlers_share_flush_and_stats_ledger(self):
        hdrs = _mixed_headers(24)
        eng, _, ring, disp, router = _dispatch_setup(depth=16, burst=8)
        router.ingest_packets(hdrs)
        f0 = eng.stats["flushes"]
        assert disp.service() == 16
        # one claim round (8 ctrl + 8 bulk), both fetches in ONE flush,
        # one trailing write-back flush
        assert eng.stats["flushes"] - f0 == 2
        dp = eng.stats["dispatch"]
        assert dp["dispatch_rounds"] == 1
        assert dp["dispatch_mixed_rounds"] == 1
        assert dp["classes"]["packet_parser_stream"]["pkts"] == 8
        assert dp["classes"]["quantize_stream"]["pkts"] == 8
        # two LC QPs in the same flush => interleaved descriptor tables
        assert eng.stats["transport"]["interleaved_batches"] >= 1
        lp = eng.stats["lc_pipeline"]
        assert lp["head"] == lp["tail"] == 2

    def test_multi_round_mixed_stream_overlaps_fetch_with_writeback(self):
        """Two claim rounds: round 2's handler fetches share a flush
        with round 1's write-backs (the lc_pipeline overlap ledger)."""
        hdrs = _mixed_headers(48)        # 16 ctrl + 16 bulk streamed
        eng, _, ring, disp, router = _dispatch_setup(depth=32, burst=8)
        router.ingest_packets(hdrs)
        f0 = eng.stats["flushes"]
        assert disp.service() == 32      # 2 rounds x 2 sub-bursts
        # flush1: round-1 fetches; flush2: round-2 fetches + round-1
        # write-backs (overlapped); flush3: trailing write-backs
        assert eng.stats["flushes"] - f0 == 3
        lp = eng.stats["lc_pipeline"]
        assert lp["overlapped_flushes"] >= 1
        assert lp["fetch_wqes_overlapped"] > 0
        assert eng.stats["dispatch"]["dispatch_mixed_rounds"] == 2

    def test_table_drop_action_never_wedges_the_ring(self):
        """Slots whose class no handler claims are swept as counted
        drops (non-handler default) instead of wedging the head."""
        eng, blk, ring, disp, router = _dispatch_setup(depth=8, burst=4)
        stray = make_roce_header(0, 0, is_rdma=False, dport=7777)
        # bypass the router's table (which would drop it at ingress):
        # a stale tag in the ring must still be reclaimed
        assert ring.push(stray, cls=0x77)
        assert ring.push(_ctrl_header(0), cls=STREAM_PARSER_WORKLOAD)
        assert disp.service() == 1           # the parser packet
        assert eng.stats["dispatch"]["dispatch_dropped_pkts"] == 1
        assert ring.space == ring.depth
        # swept slots are never reported as consumed/processed
        assert ring.stats["consumed"] == 1
        assert ring.stats["swept"] == 1
        assert eng.stats["transport"]["rx_ring_swept"] == 1
        assert eng.stats["transport"]["rx_ring_consumed"] == 1

    def test_unregistered_handler_default_still_sweeps_orphans(self):
        """A Handler default that was never registered must not suppress
        the orphan sweep — otherwise untagged slots wedge the ring
        forever."""
        eng, blk, ring, _, _ = _dispatch_setup(depth=4, burst=4)
        disp = StreamDispatcher(blk, ring,
                                MatchTable(default=Handler(0x99)),
                                burst=4)
        mr = eng.register_mr(DATA_PEER, 0, 16)
        disp.register_handler(STREAM_PARSER_WORKLOAD, DATA_PEER,
                              mr.rkey, 0)
        for i in range(4):
            assert ring.push(_ctrl_header(i))    # untagged, ring full
        assert disp.service() == 0               # no handler claims them
        assert ring.space == ring.depth          # swept, not wedged
        assert eng.stats["dispatch"]["dispatch_dropped_pkts"] == 4
        assert ring.stats["swept"] == 4 and ring.stats["consumed"] == 0
        assert ring.push(_ctrl_header(9))        # ring still usable

    def test_predict_from_stats_reports_dispatch_terms(self):
        from repro.core.rdma.simulator import predict_from_stats
        hdrs = _mixed_headers(24)
        eng, _, ring, disp, router = _dispatch_setup(depth=16, burst=8)
        router.ingest_packets(hdrs)
        disp.service()
        out = predict_from_stats(eng.stats, payload=64)
        assert out["dispatch_rounds"] == 1.0
        assert out["dispatch_mixed_share"] == 1.0
        assert out["dispatch_classes"] == 2.0
        assert out["dispatch_pkts_packet_parser_stream"] == 8.0

    def test_zero_new_compiles_after_mixed_warmup(self):
        from repro.core.rdma.transport import (descriptor_cache_size,
                                               staging_cache_size)
        hdrs = _mixed_headers(48)
        eng, _, ring, disp, router = _dispatch_setup(depth=16, burst=4)

        def cycle():
            i = 0
            while i < len(hdrs):
                n = min(24, len(hdrs) - i)
                counts = router.ingest_packets(hdrs[i:i + n])
                assert disp.service() == counts["streamed"]
                i += n

        cycle()                          # warm every shape bucket
        d0, s0 = descriptor_cache_size(), staging_cache_size()
        cycle()                          # steady state: nothing compiles
        assert descriptor_cache_size() - d0 == 0
        assert staging_cache_size() - s0 == 0

    @pytest.mark.slow
    def test_mixed_dispatch_parity_on_ici_transport(self):
        """Mixed-class dispatch on the real collective transport (forced
        2-device mesh): both handlers byte-identical to their oracles."""
        code = """
import numpy as np
import jax.numpy as jnp
from repro.core.lookaside import LookasideBlock
from repro.core.rdma import RDMAEngine
from repro.core.rdma.transport import ICITransport
from repro.core.streaming import (Drop, Forward, Handler, MatchTable,
                                  RXRing, StreamDispatcher, TrafficRouter,
                                  make_roce_header)
from repro.kernels import ref
from repro.kernels.lc_offload import (QUANT_ROW, STREAM_PARSER_WORKLOAD,
                                      STREAM_QUANT_WORKLOAD,
                                      register_default_kernels)

POOL = 1 << 15
rng = np.random.default_rng(11)
hdrs = []
for i in range(12):
    if i % 3 == 0:
        hdrs.append(make_roce_header(4, i))
    elif i % 3 == 1:
        hdrs.append(make_roce_header(0, i, is_rdma=False, dport=9000))
    else:
        h = rng.integers(0, 256, 64).astype(np.uint8)
        h[12:14] = [8, 0]; h[23] = 17; h[36:38] = [9100 >> 8, 9100 & 0xFF]
        hdrs.append(h)
hdrs = np.stack(hdrs)

eng = RDMAEngine(n_peers=2, pool_size=POOL)
assert isinstance(eng.transport, ICITransport), type(eng.transport)
blk = LookasideBlock(eng, peer=0, scratch_base=POOL // 2,
                     scratch_size=POOL // 4, pipeline_depth=2,
                     eager_writeback=False)
register_default_kernels(blk)
ring = RXRing(eng, peer=0, base=POOL - 16 * 64, depth=16)
meta_mr = eng.register_mr(1, 0, 16 * 4)
quant_mr = eng.register_mr(1, 2048, 16 * QUANT_ROW)
table = (MatchTable(default=Drop())
         .add(Forward(), priority=10, is_rdma=1)
         .add(Handler(STREAM_PARSER_WORKLOAD), udp_dport=9000)
         .add(Handler(STREAM_QUANT_WORKLOAD), udp_dport=9100))
disp = StreamDispatcher(blk, ring, table, burst=8)
disp.register_handler(STREAM_PARSER_WORKLOAD, 1, meta_mr.rkey, 0)
disp.register_handler(STREAM_QUANT_WORKLOAD, 1, quant_mr.rkey, 2048)
router = TrafficRouter(rx_ring=ring, table=table)
counts = router.ingest_packets(hdrs)
assert counts["rdma"] == 4 and counts["streamed"] == 8, counts
assert disp.service() == 8
meta = eng.read_buffer(1, 0, 16 * 4).reshape(16, 4)
np.testing.assert_array_equal(
    meta[[0, 2, 4, 6]],
    np.asarray(ref.ref_parse_packets(jnp.asarray(hdrs[1::3]))))
quant = eng.read_buffer(1, 2048, 16 * QUANT_ROW).reshape(16, QUANT_ROW)
q, s = ref.ref_quantize(jnp.asarray(hdrs[2::3].astype(np.float32)))
np.testing.assert_array_equal(quant[[1, 3, 5, 7]][:, :64],
                              np.asarray(q, np.float32))
np.testing.assert_array_equal(quant[[1, 3, 5, 7]][:, 64:],
                              np.asarray(s, np.float32))
print("ICI_DISPATCH_OK", eng.stats["dispatch"]["dispatch_mixed_rounds"])
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=560)
        assert "ICI_DISPATCH_OK" in r.stdout, r.stdout + r.stderr


class TestWrapMultiClass:
    def test_wrap_straddling_subbursts_keep_per_handler_fifo(self):
        """A claim whose sub-bursts straddle the ring wrap with classes
        interleaved keeps each handler's rows in arrival order at the
        mirrored slot indices."""
        eng, _, ring, disp, router = _dispatch_setup(depth=8, burst=8)
        first = np.stack([_ctrl_header(i) if i % 2 == 0
                          else _bulk_header() for i in range(8)])
        router.ingest_packets(first)             # seqs 0..7 fill the ring
        assert disp.service() == 8               # head = 8
        later = np.stack([_ctrl_header(10 + i) if i % 2 == 0
                          else _bulk_header() for i in range(6)])
        router.ingest_packets(later)             # seqs 8..13 wrap at 8
        w0 = ring.stats["wrap_bursts"]
        assert disp.service() == 6
        # ctrl seqs 8,10,12 / bulk 9,11,13: class gaps split spans but
        # are NOT wrap splits
        assert ring.stats["wrap_bursts"] == w0
        # now two CONSECUTIVE ctrl packets straddle the wrap (seqs 15,
        # 16 — slot 7 then slot 0): a genuine per-handler wrap split
        more = np.stack([_bulk_header(), _ctrl_header(20),
                         _ctrl_header(21), _bulk_header()])
        router.ingest_packets(more)              # seqs 14..17
        assert disp.service() == 4
        assert ring.stats["wrap_bursts"] == w0 + 1
        # the slot-mirrored output rings hold the last `depth` seqs
        # (10..17 live; 8 and 9 were overwritten by 16 and 17) — each
        # handler's live rows are in arrival order at mirrored slots
        got_ctrl = _rows(eng, 8, [10, 12, 15, 16], META_BASE, 4)
        want_ctrl = np.asarray(ref.ref_parse_packets(jnp.asarray(
            np.stack([later[2], later[4], more[1], more[2]]))))
        np.testing.assert_array_equal(got_ctrl, want_ctrl)
        got_bulk = _rows(eng, 8, [11, 13, 14, 17], QUANT_BASE,
                         QUANT_ROW)
        want_bulk = _want_quant(np.stack([later[3], later[5],
                                          more[0], more[3]]))
        np.testing.assert_array_equal(got_bulk, want_bulk)

    @pytest.mark.parametrize("policy,key", [("drop", "dropped"),
                                            ("backpressure",
                                             "backpressure")])
    def test_router_and_ring_accounting_agree_on_refusals(self, policy,
                                                          key):
        """Satellite: a full ring refusing mixed-class traffic keeps
        TrafficRouter.pkt_counters and transport rx_ring_* consistent,
        whichever policy the ring runs."""
        eng, _, ring, disp, router = _dispatch_setup(depth=4, burst=4,
                                                     policy=policy)
        hdrs = np.stack([_ctrl_header(i) if i % 2 == 0 else _bulk_header()
                         for i in range(7)])
        counts = router.ingest_packets(hdrs)
        assert counts["streamed"] == 4 and counts[key] == 3, counts
        assert router.pkt_counters[key] == ring.stats[key] == 3
        assert eng.stats["transport"]["rx_ring_" + key] == 3
        assert (router.pkt_counters["streamed"]
                == eng.stats["transport"]["rx_ring_pushed"] == 4)
        assert disp.service() == 4
        assert (ring.stats["consumed"]
                == eng.stats["transport"]["rx_ring_consumed"] == 4)
        if policy == "backpressure":     # refused packets are retryable
            retry = router.ingest_packets(hdrs[4:])
            assert retry["streamed"] == 3


class TestPrewarm:
    def test_prewarm_histogram_drops_cold_misses(self):
        from repro.core.rdma.transport import LocalTransport
        init = jnp.zeros((2, 1024), jnp.float32)
        a = LocalTransport(init)
        for i in range(6):
            a.execute_batch([("xfer", 0, 1, i, 512 + i, 24)] * 4)
            a.execute_batch([("xfer", 0, 1, i, 512 + i, 100)] * 12)
        assert a.stats["bucket_hist"] == {"8x32": 6, "16x128": 6}
        assert a.stats["cache_misses"] == 2
        b = LocalTransport(init)
        assert b.prewarm(a.stats["bucket_hist"]) == 2
        assert b.stats["prewarmed_buckets"] == 2
        np.testing.assert_array_equal(np.asarray(b.pool),
                                      np.asarray(init))
        b.execute_batch([("xfer", 0, 1, 0, 512, 24)] * 4)
        b.execute_batch([("xfer", 0, 1, 0, 512, 100)] * 12)
        assert b.stats["cache_misses"] == 0
        assert b.stats["cache_hits"] == 2
        # pair form + re-warming an already-seen bucket is a no-op
        assert b.prewarm([(8, 32)]) == 0
        # a histogram replayed from a LARGER pool clamps to this pool's
        # bucket cap, warming the key real batches will actually use
        from repro.core.rdma.transport import LocalTransport as LT
        c = LT(jnp.zeros((2, 512), jnp.float32))
        assert c.prewarm(["8x4096"]) == 1
        # length 300 -> pow2 512 == this pool's chunk cap
        c.execute_batch([("xfer", 0, 1, 0, 100, 300)] * 3)
        assert c.stats["cache_misses"] == 0

    def test_engine_transport_exposes_prewarm(self):
        eng = RDMAEngine(n_peers=2, pool_size=1024)
        assert eng.transport.prewarm([(8, 16)]) == 1
        assert eng.stats["transport"]["prewarmed_buckets"] == 1


class TestRkeyDeterminism:
    def test_engines_mint_identical_sequences(self):
        """Satellite: rkeys must not depend on process-wide registration
        history — two engines allocate the same deterministic sequence
        whatever order they were built or used in."""
        from repro.core.rdma.verbs import RKEY_BASE
        e1 = RDMAEngine(n_peers=2, pool_size=1024)
        r1 = [e1.register_mr(0, i * 64, 64).rkey for i in range(3)]
        e2 = RDMAEngine(n_peers=2, pool_size=1024)
        r2 = [e2.register_mr(0, i * 64, 64).rkey for i in range(3)]
        assert r1 == r2 == [RKEY_BASE, RKEY_BASE + 1, RKEY_BASE + 2]
        # interleaved registration does not cross-contaminate
        assert e1.register_mr(1, 0, 32).rkey == RKEY_BASE + 3
        assert e2.register_mr(1, 0, 32).rkey == RKEY_BASE + 3

    def test_module_shim_is_gone(self):
        """Satellite: the PR-5 deprecated module-global allocator is
        REMOVED — per-engine ``register_mr`` is the only rkey source."""
        from repro.core.rdma import verbs
        assert not hasattr(verbs, "next_rkey")
        assert not hasattr(verbs, "_rkey_counter")
        eng = RDMAEngine(n_peers=2, pool_size=1024)
        assert eng.register_mr(0, 0, 64).rkey == verbs.RKEY_BASE
