"""Service-chain dataplane conformance (the PR-9 tentpole).

Contracts pinned here:

* ``register_chain`` COMPOSITION VALIDATION — every stage must be a
  registered, chain-capable kernel (``stage_spec``) and the row widths
  must compose (stage i's out_row satisfies stage i+1's
  fixed/min in_row), with arity-checked stage bases;
* ingress parse→dequantize chain parity — a ≥2-stage chain over framed
  RX slots is BYTE-IDENTICAL to composing the stage computes directly,
  at slot-mirrored rows of every stage's output ring;
* inter-stage dataflow economics — stage i+1's fetch rides a later
  SHARED flush of the same grouped service pass (dataflow_msgs in the
  per-chain ledger), so the chained drive takes fewer flushes than
  draining each stage serially over the same traffic;
* egress compress→checksum production chain (``GradEgressChain``) —
  wire bytes byte-identical to ``kops.compress(chunk=64)``, checksums
  verifiable from the wire rows, the error-feedback residual equal to
  the direct ``compress_bucket`` path's because it is computed from the
  READ-BACK wire bytes;
* steady-state chain streaming compiles ZERO new descriptor or staging
  programs after one warm-up cycle;
* chaos parity — the same ingress chain over a 10%-drop wire (PR-6
  reliability layer) stays byte-identical, with retransmits > 0;
* the cost model (``simulate_chain`` / ``predict_from_stats``) reports
  the chain terms the benchmark gates;
* ICI transport (forced 2-device subprocess, slow) — the egress chain
  is byte-identical to ``kops.compress`` on the real collective
  transport too.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lookaside import LookasideBlock
from repro.core.rdma import (FaultInjector, RDMAEngine, ReliabilityConfig)
from repro.core.streaming import (Chain, Drop, GradEgressChain, MatchTable,
                                  RXRing, StreamDispatcher, make_roce_header)
from repro.core.streaming.compress import compress_bucket
from repro.kernels import ops as kops
from repro.kernels.lc_offload import (CHAIN_CHECKSUM_WORKLOAD,
                                      CHAIN_COMPRESS_WORKLOAD,
                                      CHAIN_DEQUANT_WORKLOAD,
                                      CHAIN_PARSE_WORKLOAD, FRAME_ROW,
                                      HDR_BYTES, PARSED_ROW, QUANT_ROW,
                                      STREAM_PARSER_WORKLOAD,
                                      _dequant_trailing_rows,
                                      _parse_frame_rows,
                                      register_chain_kernels,
                                      register_default_kernels)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

POOL = 1 << 15
DATA_PEER, LC_PEER = 1, 0
DEPTH = 8


def _ingress_setup(eng=None, depth=DEPTH, burst=4, pipeline_depth=4):
    """Framed RX ring (129-word slots) + a parse→dequantize chain as the
    table DEFAULT, both stage rings slot-mirrored on the data peer."""
    eng = eng or RDMAEngine(n_peers=2, pool_size=POOL)
    blk = LookasideBlock(eng, peer=LC_PEER, scratch_base=POOL // 2,
                         scratch_size=POOL // 4, eager_writeback=False,
                         pipeline_depth=pipeline_depth)
    register_chain_kernels(blk)
    ring = RXRing(eng, peer=LC_PEER, base=0, depth=depth,
                  slot_bytes=FRAME_ROW)
    chain = Chain((CHAIN_PARSE_WORKLOAD, CHAIN_DEQUANT_WORKLOAD),
                  name="ingress")
    disp = StreamDispatcher(blk, ring, MatchTable(default=chain),
                            burst=burst)
    s1 = FRAME_ROW * depth + 64
    s2 = s1 + PARSED_ROW * depth
    mr = eng.register_mr(DATA_PEER, s1, (PARSED_ROW + HDR_BYTES) * depth)
    disp.register_chain(chain, DATA_PEER, mr.rkey, [s1, s2])
    return eng, blk, ring, disp, chain, (s1, s2)


def _frames(n, seed=0):
    """n framed ingress slots: 64 header bytes ‖ 65-word quant payload
    (64 int8 lanes as f32 + one fp32 scale)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        hdr = make_roce_header(4, 100 + i, is_rdma=False, dport=9000)
        payload = np.concatenate([
            rng.integers(-127, 128, 64).astype(np.float32),
            np.asarray([rng.uniform(0.01, 2.0)], np.float32)])
        out.append(np.concatenate([hdr.astype(np.float32), payload]))
    return np.stack(out)


def _drive(ring, disp, frames, depth):
    """Push in ring-sized windows, one service pass per window."""
    pushed = 0
    for f in frames:
        if pushed == depth:
            disp.service()
            pushed = 0
        assert ring.push(f)              # untagged: the default chain owns it
        pushed += 1
    disp.service()


def _stage_rows(eng, base, row, depth, seqs):
    rows = eng.read_buffer(DATA_PEER, base, depth * row
                           ).reshape(depth, row)
    return np.stack([rows[s % depth] for s in seqs])


class TestChainRegistrationValidation:
    def _disp(self, slot_bytes=HDR_BYTES, chain_kernels=True):
        eng = RDMAEngine(n_peers=2, pool_size=POOL)
        blk = LookasideBlock(eng, peer=LC_PEER, scratch_base=POOL // 2,
                             scratch_size=POOL // 4)
        if chain_kernels:
            register_chain_kernels(blk)
        else:
            register_default_kernels(blk)
        ring = RXRing(eng, peer=LC_PEER, base=0, depth=4,
                      slot_bytes=slot_bytes)
        mr = eng.register_mr(DATA_PEER, 0, 2048)
        return eng, StreamDispatcher(blk, ring, MatchTable()), mr

    def test_unregistered_stage_rejected(self):
        _, disp, mr = self._disp()
        with pytest.raises(KeyError, match="not registered"):
            disp.register_chain(Chain((0x77,)), DATA_PEER, mr.rkey, [0])

    def test_non_chain_capable_stage_rejected(self):
        """A plain handler kernel (no stage_spec) cannot sit in a
        pipeline — the dispatcher needs its row geometry."""
        _, disp, mr = self._disp(chain_kernels=False)
        with pytest.raises(TypeError, match="not chain-capable"):
            disp.register_chain(Chain((STREAM_PARSER_WORKLOAD,)),
                                DATA_PEER, mr.rkey, [0])

    def test_row_widths_must_compose(self):
        # parse demands FRAME_ROW-word input; a 64-word ring can't feed it
        _, disp, mr = self._disp(slot_bytes=HDR_BYTES)
        with pytest.raises(ValueError, match="in_row == 129"):
            disp.register_chain(Chain((CHAIN_PARSE_WORKLOAD,)),
                                DATA_PEER, mr.rkey, [0])
        # dequantize demands >= QUANT_ROW trailing words
        _, disp, mr = self._disp(slot_bytes=32)
        with pytest.raises(ValueError, match="in_row >= 65"):
            disp.register_chain(Chain((CHAIN_DEQUANT_WORKLOAD,)),
                                DATA_PEER, mr.rkey, [0])
        # compress (64 in) -> checksum composes; compress -> parse doesn't
        _, disp, mr = self._disp(slot_bytes=HDR_BYTES)
        disp.register_chain(
            Chain((CHAIN_COMPRESS_WORKLOAD, CHAIN_CHECKSUM_WORKLOAD)),
            DATA_PEER, mr.rkey, [0, 1024])
        with pytest.raises(ValueError, match="in_row == 129"):
            disp.register_chain(
                Chain((CHAIN_COMPRESS_WORKLOAD, CHAIN_PARSE_WORKLOAD)),
                DATA_PEER, mr.rkey, [0, 1024])

    def test_stage_bases_arity_checked(self):
        _, disp, mr = self._disp()
        with pytest.raises(ValueError, match="stage_bases"):
            disp.register_chain(
                Chain((CHAIN_COMPRESS_WORKLOAD, CHAIN_CHECKSUM_WORKLOAD)),
                DATA_PEER, mr.rkey, [0])
        with pytest.raises(TypeError, match="expected a Chain"):
            disp.register_chain(Drop(), DATA_PEER, mr.rkey, [])


class TestIngressChainParity:
    def test_parse_dequant_byte_identical_to_composed_oracles(self):
        """13 framed packets through parse→dequantize, windows of 8:
        every still-live slot of BOTH stage output rings is byte-equal
        to composing the stage computes directly."""
        eng, _, ring, disp, _, (s1, s2) = _ingress_setup()
        frames = _frames(13)
        _drive(ring, disp, frames, DEPTH)
        o1 = _parse_frame_rows(frames)
        o2 = _dequant_trailing_rows(o1)
        # slots are reused across windows: seqs 0..4 were overwritten by
        # 8..12, so rows 5..12 are the live, checkable set
        live = list(range(5, 13))
        np.testing.assert_array_equal(
            _stage_rows(eng, s1, PARSED_ROW, DEPTH, live),
            np.asarray(o1)[live])
        np.testing.assert_array_equal(
            _stage_rows(eng, s2, HDR_BYTES, DEPTH, live),
            np.asarray(o2)[live])
        assert ring.space == ring.depth          # all RX slots freed

    def test_per_chain_ledger_and_dataflow_accounting(self):
        eng, _, ring, disp, _, _ = _ingress_setup()
        _drive(ring, disp, _frames(13), DEPTH)
        led = eng.stats["dispatch"]["chains"]["ingress"]
        # 13 pkts at burst 4 -> 4 stage-0 claims; each runs both stages
        assert led == {"pkts": 13, "bursts": 4, "stages": 2,
                       "stage_invocations": 8, "wqes": 8,
                       "dataflow_msgs": 4, "completed_pkts": 13}
        assert eng.stats["dispatch"]["dispatch_rounds"] >= 4

    def test_chained_flushes_below_staged_serial_sum(self):
        """The dataflow win: driving the chain takes fewer engine
        flushes than draining each stage serially over the same rows,
        because stage 2's fetches ride flushes the grouped pass already
        pays for. (Needs multiple claim rounds per pass — burst <
        window — to have flushes to share.)"""
        depth, burst = 16, 4
        frames = _frames(32)
        eng, _, ring, disp, _, _ = _ingress_setup(depth=depth, burst=burst)
        f0 = eng.stats["flushes"]
        _drive(ring, disp, frames, depth)
        chained = eng.stats["flushes"] - f0

        def single_stage_flushes(stage_wid, rows, slot_bytes, out_row):
            eng = RDMAEngine(n_peers=2, pool_size=POOL)
            blk = LookasideBlock(eng, peer=LC_PEER,
                                 scratch_base=POOL // 2,
                                 scratch_size=POOL // 4,
                                 eager_writeback=False, pipeline_depth=4)
            register_chain_kernels(blk)
            ring = RXRing(eng, peer=LC_PEER, base=0, depth=depth,
                          slot_bytes=slot_bytes)
            chain = Chain((stage_wid,))
            disp = StreamDispatcher(blk, ring, MatchTable(default=chain),
                                    burst=burst)
            base = slot_bytes * depth + 64
            mr = eng.register_mr(DATA_PEER, base, out_row * depth)
            disp.register_chain(chain, DATA_PEER, mr.rkey, [base])
            f0 = eng.stats["flushes"]
            _drive(ring, disp, rows, depth)
            return eng.stats["flushes"] - f0

        o1 = np.asarray(_parse_frame_rows(frames))
        staged = (single_stage_flushes(CHAIN_PARSE_WORKLOAD, frames,
                                       FRAME_ROW, PARSED_ROW)
                  + single_stage_flushes(CHAIN_DEQUANT_WORKLOAD, o1,
                                         PARSED_ROW, HDR_BYTES))
        assert chained < staged, (chained, staged)
        assert (chained, staged) == (10, 12)     # deterministic machine

    def test_zero_new_compiles_after_chain_warmup(self):
        from repro.core.rdma.transport import (descriptor_cache_size,
                                               staging_cache_size)
        eng, _, ring, disp, _, _ = _ingress_setup()
        _drive(ring, disp, _frames(13), DEPTH)      # warm every bucket
        d0, s0 = descriptor_cache_size(), staging_cache_size()
        _drive(ring, disp, _frames(13, seed=7), DEPTH)
        assert descriptor_cache_size() - d0 == 0
        assert staging_cache_size() - s0 == 0

    def test_non_default_chain_coexists_with_orphan_sweep(self):
        """A chain bound to a non-default entry claims only its tag;
        stray tags are swept as counted drops, never wedging the ring."""
        eng, blk, ring, _, chain, (s1, s2) = _ingress_setup()
        disp = StreamDispatcher(
            blk, ring, MatchTable(default=Drop()).add(chain, udp_dport=9000),
            burst=4)
        mr = eng.register_mr(DATA_PEER, s1 + POOL // 4,
                             (PARSED_ROW + HDR_BYTES) * DEPTH)
        disp.register_chain(chain, DATA_PEER, mr.rkey,
                            [s1 + POOL // 4, s2 + POOL // 4])
        frames = _frames(4)
        for f in frames[:2]:
            assert ring.push(f, cls=chain.tag)
        for f in frames[2:]:
            assert ring.push(f, cls=0x77)        # nobody owns this tag
        assert disp.service() == 2
        led = eng.stats["dispatch"]["chains"]["ingress"]
        assert led["pkts"] == led["completed_pkts"] == 2
        assert eng.stats["dispatch"]["dispatch_dropped_pkts"] == 2
        assert ring.space == ring.depth


class TestEgressChain:
    def _chain(self, eng=None, depth=16, burst=8):
        eng = eng or RDMAEngine(n_peers=2, pool_size=POOL)
        ch = GradEgressChain(eng, data_peer=DATA_PEER, ring_base=1024,
                             out_base=4096, lc_peer=LC_PEER,
                             scratch_base=POOL // 2,
                             scratch_size=POOL // 4, depth=depth,
                             burst=burst)
        return eng, ch

    def test_wire_parity_checksums_and_residual(self):
        """q/s wire rows byte-equal to kops.compress(chunk=64); the
        checksum stage's stamps verify from those rows; the residual
        (computed from READ-BACK wire bytes) equals the direct
        compress_bucket path's."""
        eng, ch = self._chain()
        flat = np.random.default_rng(2).normal(size=500).astype(np.float32)
        resid0 = np.zeros(500, np.float32)
        q, s, csum, resid = ch.compress(flat, resid0)
        kq, ks, _ = kops.compress(jnp.asarray(np.pad(flat, (0, 12))),
                                  chunk=64)
        np.testing.assert_array_equal(q, np.asarray(kq))
        np.testing.assert_array_equal(s, np.asarray(ks))
        assert GradEgressChain.verify_checksums(q, s, csum)
        _, _, want_resid = compress_bucket(jnp.asarray(flat),
                                           jnp.asarray(resid0), chunk=64)
        np.testing.assert_array_equal(resid, np.asarray(want_resid))
        # corrupting one wire word must break verification
        q_bad = q.copy()
        q_bad[0, 3] += 1
        assert not GradEgressChain.verify_checksums(q_bad, s, csum)

    def test_multi_window_error_feedback_rounds(self):
        """A bucket larger than the ring (20 rows through a depth-16
        ring) across two error-feedback rounds matches the direct path
        round for round."""
        eng, ch = self._chain(depth=16, burst=8)
        rng = np.random.default_rng(5)
        flat1 = rng.normal(size=1280).astype(np.float32)
        flat2 = rng.normal(size=1280).astype(np.float32)
        resid = np.zeros(1280, np.float32)
        want_resid = jnp.zeros(1280, jnp.float32)
        for flat in (flat1, flat2):
            q, s, csum, resid = ch.compress(flat, resid)
            wq, ws, want_resid = compress_bucket(
                jnp.asarray(flat), want_resid, chunk=64)
            np.testing.assert_array_equal(q, np.asarray(wq))
            np.testing.assert_array_equal(s, np.asarray(ws))
            np.testing.assert_array_equal(resid, np.asarray(want_resid))
            assert GradEgressChain.verify_checksums(q, s, csum)
        led = eng.stats["dispatch"]["chains"]["grad_egress"]
        assert led["pkts"] == led["completed_pkts"] == 40
        assert led["stages"] == 2
        # every claim ran both stages; windows of 16 at burst 8
        assert led["stage_invocations"] == 2 * led["bursts"]
        assert led["dataflow_msgs"] == led["bursts"]


class TestChainChaos:
    def test_ingress_chain_parity_under_seeded_drop(self):
        """10% seeded wire drop (PR-6 reliability layer): every stage
        fetch and write-back is retransmitted until it lands — chain
        output stays byte-identical and the pipeline ledger completes."""
        eng = RDMAEngine(n_peers=2, pool_size=POOL, scheduler="drr",
                         flush_budget=8)
        eng.install_fault_injector(
            FaultInjector(3, drop=0.10, corrupt=0.03),
            ReliabilityConfig(retry_cnt=16))
        eng, _, ring, disp, _, (s1, s2) = _ingress_setup(eng=eng)
        frames = _frames(13)
        _drive(ring, disp, frames, DEPTH)
        o1 = _parse_frame_rows(frames)
        o2 = _dequant_trailing_rows(o1)
        live = list(range(5, 13))
        np.testing.assert_array_equal(
            _stage_rows(eng, s1, PARSED_ROW, DEPTH, live),
            np.asarray(o1)[live])
        np.testing.assert_array_equal(
            _stage_rows(eng, s2, HDR_BYTES, DEPTH, live),
            np.asarray(o2)[live])
        led = eng.stats["dispatch"]["chains"]["ingress"]
        assert led["completed_pkts"] == 13
        assert eng.stats["reliability"]["retransmits"] > 0


class TestChainModel:
    def test_simulate_chain_flush_identities(self):
        from repro.core.rdma.simulator import simulate_chain
        r = simulate_chain(1024, rows=(FRAME_ROW, PARSED_ROW, HDR_BYTES),
                           burst=32, pipeline_depth=4)
        assert r["stages"] == 2 and r["bursts"] == 32
        assert r["chained_flushes"] == 32 + 2 * 2
        assert r["staged_flushes"] == 2 * (32 + 1)
        assert r["flush_ratio"] > 1
        assert r["chained_speedup_vs_staged"] > 1
        # a 1-stage chain degenerates to the single-class drain shape
        r1 = simulate_chain(64, rows=(64, 4), burst=32)
        assert r1["chained_flushes"] == 4 and r1["staged_flushes"] == 3
        with pytest.raises(ValueError):
            simulate_chain(0, rows=(64, 4))

    def test_predict_from_stats_reports_chain_terms(self):
        from repro.core.rdma.simulator import predict_from_stats
        eng, _, ring, disp, _, _ = _ingress_setup()
        _drive(ring, disp, _frames(13), DEPTH)
        out = predict_from_stats(eng.stats, payload=64)
        assert out["dispatch_chains"] == 1.0
        assert out["chain_pkts_ingress"] == 13.0
        assert out["chain_stages_ingress"] == 2.0
        assert out["chain_stage_invocations_ingress"] == 8.0
        assert out["chain_dataflow_msgs_ingress"] == 4.0
        assert out["chain_completion_ingress"] == 1.0


@pytest.mark.slow
class TestICIChain:
    def test_egress_chain_parity_on_ici_transport(self):
        """The compress→checksum chain on the real collective transport
        (forced 2-device mesh): wire bytes byte-identical to
        kops.compress, checksums verified."""
        code = """
import numpy as np
import jax.numpy as jnp
from repro.core.rdma import RDMAEngine
from repro.core.rdma.transport import ICITransport
from repro.core.streaming import GradEgressChain
from repro.kernels import ops as kops

POOL = 1 << 15
eng = RDMAEngine(n_peers=2, pool_size=POOL)
assert isinstance(eng.transport, ICITransport), type(eng.transport)
ch = GradEgressChain(eng, data_peer=1, ring_base=1024, out_base=4096,
                     lc_peer=0, scratch_base=POOL // 2,
                     scratch_size=POOL // 4, depth=8, burst=4,
                     pipeline_depth=2)
flat = np.random.default_rng(9).normal(size=640).astype(np.float32)
q, s, csum, resid = ch.compress(flat, np.zeros(640, np.float32))
kq, ks, _ = kops.compress(jnp.asarray(flat), chunk=64)
assert np.array_equal(q, np.asarray(kq))
assert np.array_equal(s, np.asarray(ks))
assert GradEgressChain.verify_checksums(q, s, csum)
led = eng.stats["dispatch"]["chains"]["grad_egress"]
assert led["completed_pkts"] == 10, led
print("ICI_CHAIN_OK", led["stage_invocations"])
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=560)
        assert "ICI_CHAIN_OK" in r.stdout, r.stdout + r.stderr
