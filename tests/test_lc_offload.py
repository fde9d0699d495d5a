"""Lookaside offload conformance: LC kernels as first-class clients of the
shared engine.

Contracts pinned here:

* each registered offload kernel's RDMA-read -> compute -> RDMA-write
  result is BYTE-identical to the host-side oracle in ``kernels/ref.py``,
  on both transports (LocalTransport here, ICITransport in a forced
  multi-device subprocess);
* LC WQEs land in the SAME descriptor table as concurrent host verbs
  traffic (``interleaved_batches``; ``qp_service``/``lc_service``);
* StatusMsg completion is CQE-driven: with a deferred write-back the
  status appears only after a (host-driven) flush executes the write-back
  WQE — in poll AND interrupt mode;
* engine-level failures (bad rkey) surface as ``StatusMsg(ok=False)``,
  control-FIFO overflow as a *retryable* ``StatusMsg(ok=False)`` — no
  RuntimeError unwinds the engine loop (the FIFO backpressure fix);
* LC contention terms flow through ``predict_from_stats``.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lookaside import ControlMsg, FIFO, LookasideBlock
from repro.core.rdma import Opcode, RDMAEngine, WQE
from repro.kernels import ref
from repro.kernels.lc_offload import (MM_WORKLOAD, PARSER_WORKLOAD,
                                      register_default_kernels)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

RNG = np.random.default_rng(7)
DATA_PEER = 1            # remote peer holding operands/results
LC_PEER = 0              # the NIC the LC block rides


def _engine(**kw):
    kw.setdefault("n_peers", 2)
    kw.setdefault("pool_size", 1 << 14)
    eng = RDMAEngine(**kw)
    blk = LookasideBlock(eng, peer=LC_PEER, scratch_base=1 << 13)
    register_default_kernels(blk)
    return eng, blk


def _place_mm(eng, m, k, n):
    A = RNG.standard_normal((m, k)).astype(np.float32)
    B = RNG.standard_normal((k, n)).astype(np.float32)
    a_addr, b_addr = 0, m * k
    out_addr = m * k + k * n
    mr = eng.register_mr(DATA_PEER, 0, out_addr + m * n)
    eng.write_buffer(DATA_PEER, a_addr, A.ravel())
    eng.write_buffer(DATA_PEER, b_addr, B.ravel())
    return A, B, mr, (a_addr, b_addr, out_addr)


def _roce_packets(n_pkts):
    pkts = RNG.integers(0, 256, size=(n_pkts, 64)).astype(np.uint8)
    pkts[::2, 12:14] = [0x08, 0x00]      # IPv4
    pkts[::2, 23] = 17                   # UDP
    pkts[::2, 36:38] = [18, 183]         # dport 4791 (RoCEv2)
    return pkts


class TestOffloadParity:
    @pytest.mark.parametrize("m,k,n", [(8, 16, 12), (16, 32, 8),
                                       (4, 128, 4),
                                       (520, 8, 4)])   # m padded to 640
    def test_systolic_mm_byte_identical_to_host_reference(self, m, k, n):
        eng, blk = _engine()
        A, B, mr, (a, b, out) = _place_mm(eng, m, k, n)
        assert blk.dispatch(ControlMsg(
            MM_WORKLOAD, (DATA_PEER, mr.rkey, a, b, out, m, k, n),
            tag=3)) is None
        st = blk.poll(MM_WORKLOAD)
        assert st is not None and st.ok and st.tag == 3
        assert st.result_addr == out
        got = eng.read_buffer(DATA_PEER, out, m * n).reshape(m, n)
        want = np.asarray(ref.ref_matmul(jnp.asarray(A), jnp.asarray(B)))
        np.testing.assert_array_equal(got, want)      # byte-identical

    def test_packet_parser_byte_identical_to_host_reference(self):
        eng, blk = _engine()
        n_pkts = 32
        pkts = _roce_packets(n_pkts)
        p_addr, out_addr = 0, n_pkts * 64
        mr = eng.register_mr(DATA_PEER, 0, n_pkts * 64 + n_pkts * 4)
        eng.write_buffer(DATA_PEER, p_addr, pkts.astype(np.float32).ravel())
        blk.dispatch(ControlMsg(
            PARSER_WORKLOAD, (DATA_PEER, mr.rkey, p_addr, n_pkts, out_addr),
            tag=4))
        st = blk.poll(PARSER_WORKLOAD)
        assert st is not None and st.ok
        got = eng.read_buffer(DATA_PEER, out_addr, n_pkts * 4
                              ).reshape(n_pkts, 4)
        want = np.asarray(ref.ref_parse_packets(jnp.asarray(pkts)))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.slow
    def test_offload_parity_on_ici_transport(self):
        """Both kernels on the real collective transport (forced 2-device
        mesh): byte-identical to the oracles."""
        code = """
import numpy as np
import jax.numpy as jnp
from repro.core.rdma import RDMAEngine
from repro.core.rdma.transport import ICITransport
from repro.core.lookaside import ControlMsg, LookasideBlock
from repro.kernels import ref
from repro.kernels.lc_offload import (MM_WORKLOAD, PARSER_WORKLOAD,
                                      register_default_kernels)

eng = RDMAEngine(n_peers=2, pool_size=1 << 14)
assert isinstance(eng.transport, ICITransport), type(eng.transport)
blk = LookasideBlock(eng, peer=0, scratch_base=1 << 13)
register_default_kernels(blk)
rng = np.random.default_rng(11)

m, k, n = 8, 16, 12
A = rng.standard_normal((m, k)).astype(np.float32)
B = rng.standard_normal((k, n)).astype(np.float32)
mr = eng.register_mr(1, 0, 4096)
eng.write_buffer(1, 0, A.ravel())
eng.write_buffer(1, m * k, B.ravel())
out = m * k + k * n
blk.dispatch(ControlMsg(MM_WORKLOAD, (1, mr.rkey, 0, m * k, out, m, k, n)))
st = blk.poll(MM_WORKLOAD)
assert st is not None and st.ok, st
got = eng.read_buffer(1, out, m * n).reshape(m, n)
want = np.asarray(ref.ref_matmul(jnp.asarray(A), jnp.asarray(B)))
np.testing.assert_array_equal(got, want)

n_pkts = 16
pkts = rng.integers(0, 256, size=(n_pkts, 64)).astype(np.uint8)
pkts[::2, 12:14] = [8, 0]; pkts[::2, 23] = 17; pkts[::2, 36:38] = [18, 183]
base = 2048
mr2 = eng.register_mr(1, base, n_pkts * 68)
eng.write_buffer(1, base, pkts.astype(np.float32).ravel())
blk.dispatch(ControlMsg(
    PARSER_WORKLOAD, (1, mr2.rkey, base, n_pkts, base + n_pkts * 64)))
st = blk.poll(PARSER_WORKLOAD)
assert st is not None and st.ok, st
got = eng.read_buffer(1, base + n_pkts * 64, n_pkts * 4).reshape(n_pkts, 4)
np.testing.assert_array_equal(
    got, np.asarray(ref.ref_parse_packets(jnp.asarray(pkts))))
print("ICI_LC_OK", eng.stats["lc_wqes"])
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=560)
        assert "ICI_LC_OK" in r.stdout, r.stdout + r.stderr


class TestSharedEngineContention:
    def test_lc_wqes_share_descriptor_table_with_host_traffic(self):
        """The acceptance criterion: one LC invocation's WQEs are
        scheduled into the same flush as concurrent host verbs traffic —
        interleaved_batches fires and both parties appear in the service
        ledger (LC QPs also in lc_service)."""
        eng, blk = _engine(scheduler="drr", flush_budget=8)
        A, B, mr, (a, b, out) = _place_mm(eng, 8, 16, 8)
        hqp = eng.create_qp(LC_PEER, DATA_PEER)
        for i in range(6):
            eng.post_send(hqp, WQE(
                Opcode.READ, hqp.qp_num, wr_id=i, local_addr=6000 + i,
                remote_addr=i, length=1, rkey=mr.rkey))
        eng.ring_sq_doorbell(hqp, defer=True)      # host armed, not flushed
        i0 = eng.stats["transport"]["interleaved_batches"]

        blk.dispatch(ControlMsg(
            MM_WORKLOAD, (DATA_PEER, mr.rkey, a, b, out, 8, 16, 8), tag=1))
        assert blk.poll(MM_WORKLOAD).ok
        assert eng.stats["transport"]["interleaved_batches"] > i0
        lc_qp = blk.kernels[MM_WORKLOAD].qps[DATA_PEER]
        assert eng.stats["qp_service"][hqp.qp_num] > 0
        assert eng.stats["qp_service"][lc_qp.qp_num] == 3   # 2 READ + 1 WRITE
        assert eng.stats["lc_service"] == {lc_qp.qp_num: 3}
        assert eng.stats["lc_wqes"] == 3
        # latency histogram ledger covers every serviced WQE
        for q in (hqp.qp_num, lc_qp.qp_num):
            assert (sum(eng.stats["qp_latency_us"][q].values())
                    == eng.stats["qp_service"][q])
        while hqp.pending():
            eng.flush_doorbells()
        assert [c.wr_id for c in eng.poll_cq(hqp, 64)] == list(range(6))

    def test_predict_from_stats_carries_lc_contention_terms(self):
        from repro.core.rdma.simulator import predict_from_stats
        eng, blk = _engine(scheduler="drr", flush_budget=8)
        A, B, mr, (a, b, out) = _place_mm(eng, 8, 16, 8)
        hqp = eng.create_qp(LC_PEER, DATA_PEER)
        for i in range(5):
            eng.post_send(hqp, WQE(
                Opcode.READ, hqp.qp_num, wr_id=i, local_addr=6000 + i,
                remote_addr=i, length=1, rkey=mr.rkey))
        eng.ring_sq_doorbell(hqp, defer=True)
        blk.dispatch(ControlMsg(
            MM_WORKLOAD, (DATA_PEER, mr.rkey, a, b, out, 8, 16, 8), tag=1))
        while hqp.pending():
            eng.flush_doorbells()
        m = predict_from_stats(eng.stats, payload=4096, op="read")
        assert m["lc_wqes"] == 3.0
        assert 0.0 < m["lc_share"] < 1.0
        assert m["lc_contention_s"] > 0.0
        assert m["host_jain_index"] == 1.0       # single host QP
        assert m["host_slowdown_from_lc"] > 1.0
        # byte ledger: LC moved A+B+C, host moved its 5 single-word reads
        lc_qp = blk.kernels[MM_WORKLOAD].qps[DATA_PEER]
        assert eng.stats["qp_bytes"][lc_qp.qp_num] == 8 * 16 + 16 * 8 + 8 * 8
        assert eng.stats["qp_bytes"][hqp.qp_num] == 5


class TestCQEDrivenStatus:
    def test_statusmsg_appears_only_after_writeback_cqe_poll_mode(self):
        eng, blk = _engine()
        blk.eager_writeback = False       # leave the write-back armed
        A, B, mr, (a, b, out) = _place_mm(eng, 8, 16, 8)
        blk.dispatch(ControlMsg(
            MM_WORKLOAD, (DATA_PEER, mr.rkey, a, b, out, 8, 16, 8), tag=2))
        # kernel fn is done, but the write-back WQE has not executed:
        # no StatusMsg yet, and the remote result region is still zeros
        assert blk.poll(MM_WORKLOAD) is None
        assert not np.any(eng.read_buffer(DATA_PEER, out, 8 * 8))
        eng.flush_doorbells()             # a HOST-driven flush completes it
        st = blk.poll(MM_WORKLOAD)
        assert st is not None and st.ok and st.tag == 2
        got = eng.read_buffer(DATA_PEER, out, 8 * 8).reshape(8, 8)
        np.testing.assert_array_equal(
            got, np.asarray(ref.ref_matmul(jnp.asarray(A), jnp.asarray(B))))

    def test_statusmsg_interrupt_mode_fires_on_cqe(self):
        eng, blk = _engine()
        blk.eager_writeback = False
        seen = []
        blk.register_interrupt(MM_WORKLOAD, seen.append)
        A, B, mr, (a, b, out) = _place_mm(eng, 8, 16, 8)
        blk.dispatch(ControlMsg(
            MM_WORKLOAD, (DATA_PEER, mr.rkey, a, b, out, 8, 16, 8), tag=6))
        assert seen == []                 # not before the write-back CQE
        eng.flush_doorbells()
        assert len(seen) == 1 and seen[0].ok and seen[0].tag == 6

    def test_engine_failure_surfaces_as_not_ok_status(self):
        eng, blk = _engine()
        A, B, mr, (a, b, out) = _place_mm(eng, 8, 16, 8)
        blk.dispatch(ControlMsg(
            MM_WORKLOAD, (DATA_PEER, 0xBAD, a, b, out, 8, 16, 8), tag=8))
        st = blk.poll(MM_WORKLOAD)
        assert st is not None and not st.ok and not st.retryable
        assert "remote_access_error" in st.detail
        assert blk.stats["errors"] == 1


class TestFIFOBackpressure:
    def test_dispatch_backpressure_is_retryable_status_not_raise(self):
        """Regression for the FIFO.push RuntimeError: a full control FIFO
        must surface as a retryable StatusMsg(ok=False) — the engine loop
        never sees an exception — and the same message dispatches fine
        after the queue drains."""
        eng, blk = _engine()
        k = blk.kernels[MM_WORKLOAD]
        k.control_fifo = FIFO(depth=2)
        A, B, mr, (a, b, out) = _place_mm(eng, 8, 16, 8)
        args = (DATA_PEER, mr.rkey, a, b, out, 8, 16, 8)
        # fabric busy: enqueue without servicing until the FIFO fills
        assert blk.dispatch(ControlMsg(MM_WORKLOAD, args, tag=1),
                            service=False) is None
        assert blk.dispatch(ControlMsg(MM_WORKLOAD, args, tag=2),
                            service=False) is None
        st = blk.dispatch(ControlMsg(MM_WORKLOAD, args, tag=3),
                          service=False)
        assert st is not None and not st.ok and st.retryable
        assert st.tag == 3 and "backpressure" in st.detail
        assert blk.stats["backpressure"] == 1
        blk.service(MM_WORKLOAD)          # fabric drains the queue
        assert blk.poll(MM_WORKLOAD).tag == 1
        assert blk.poll(MM_WORKLOAD).tag == 2
        # the rejected message retries cleanly
        assert blk.dispatch(ControlMsg(MM_WORKLOAD, args, tag=3)) is None
        assert blk.poll(MM_WORKLOAD).tag == 3

    def test_raw_fifo_push_still_raises_try_push_does_not(self):
        f = FIFO(depth=1)
        assert f.try_push(1)
        assert not f.try_push(2)          # backpressure, no raise
        with pytest.raises(RuntimeError, match="backpressure"):
            f.push(3)
        assert len(f) == 1
