"""Offloaded Lookaside kernels (paper §IV-C/§IV-D, run as engine clients).

Each kernel here follows the paper's offload contract end to end:
RDMA-read its operands from a *remote* peer over the shared engine (WQEs
on the kernel's own QP, scheduled into the same descriptor tables as host
verbs traffic), compute on the NIC — the Pallas kernels that map onto the
TPU MXU/VPU — and RDMA-write the result back. The host only exchanges
``ControlMsg``/``StatusMsg``; the data never crosses PCIe.

ControlMsg argument conventions (all ints unless noted):

  ``systolic_mm``   : (remote_peer, rkey, a_addr, b_addr, out_addr, m, k, n)
  ``packet_parser`` : (remote_peer, rkey, pkts_addr, n_pkts, out_addr)
  stream handlers (built by the dispatch plane's ``StreamDispatcher``,
  not the host): (ring_peer, ring_rkey, ring_base, out_peer, out_rkey,
  out_base, spans) — ``spans`` is the sub-burst's tuple of contiguous
  RX-ring ``(addr, count)`` slot spans in arrival order (≤ 2 for a
  whole-ring burst; more when a mixed-class claim interleaves with
  other handlers' slots).
  chain stages (the dispatcher's ``Chain`` pipelines): the stream-handler
  args plus a trailing ``in_row`` — the INPUT row width in pool words —
  because a chain stage's source region is either the RX ring (stage 0)
  or the upstream stage's slot-mirrored output ring, whose row width the
  upstream kernel owns. Slot index recovery is
  ``(addr - in_base) // in_row`` at any stage position. Each chain-stage
  kernel publishes a ``ChainStageSpec`` (its ``out_row`` plus input-width
  constraints) that ``register_chain`` composes and validates.

Stream handlers registered here (the dispatch-plane handler mix):

  ``packet_parser_stream`` — the ctrl-class handler: parse each slot's
  RoCEv2-style header into a 4-word meta row (one row per slot in the
  class-mirrored meta ring).
  ``quantize_stream``      — the bulk-class handler: int8-quantize each
  slot's 64-lane payload (``kernels/quantize_stream.py``, the Streaming
  Compute block's in-flight gradient-compression kernel — see
  ``streaming/compress.py`` for its error-feedback system role), writing
  a 65-word row per slot (64 int8 values as f32 + the fp32 scale).

Chain stages registered here (``register_chain_kernels``) — each one a
generator with the same fetch → ``yield`` → compute/write-back shape as
the stream handlers, composable into ``Chain`` pipelines:

  ``chain_parse``    — ingress head over FRAMED slots (64 header bytes +
  a 65-word quant payload per slot, ``FRAME_ROW`` = 129 words): parse
  the header with the same Pallas program as the stream parser and emit
  [meta(4) ‖ payload(65)] rows (``PARSED_ROW`` = 69).
  ``chain_dequant``  — consume the TRAILING ``QUANT_ROW`` words of each
  input row (int8 lanes as f32 + scale) and emit the dequantized 64-lane
  f32 row, reusing ``_stream_dequant``'s cached jitted programs.
  ``chain_compress`` — egress head: int8-quantize 64-lane f32 rows into
  65-word [q ‖ scale] rows via ``_stream_quant`` (byte parity with
  ``kops.compress(x, chunk=64)``).
  ``chain_checksum`` — egress tail over ANY row width: a 2-word
  [checksum, width] row per input row, the checksum a position-weighted
  sum of the words' raw bit patterns mod 2^24 (exact in the f32 pool) —
  the wire-integrity stamp of the compress→checksum gradient chain.

Correctness contract: outputs are byte-identical to the oracles in
``repro.kernels.ref`` run on the same backend over the same operand bytes
(for the matmul, with a single K-block so the fp32 accumulation order
matches the oracle's; for the quantizer, ``ref_quantize`` row-wise).
Across backends they are not: a TPU's f32 division can move an int8 code
by one step at a .5 tie, and an f32 matmul on the MXU multiplies in bf16
passes. ``chip_smoke.py`` holds the chip to those bounds.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.packet_parser import HDR_BYTES, parse_packets
from repro.kernels.quantize_stream import dequantize_stream, quantize_stream
from repro.kernels.systolic_mm import systolic_mm

MM_WORKLOAD = 0x10
PARSER_WORKLOAD = 0x11
STREAM_PARSER_WORKLOAD = 0x12
STREAM_QUANT_WORKLOAD = 0x13

#: chain-stage workload ids (0x20+ keeps them disjoint from handlers)
CHAIN_PARSE_WORKLOAD = 0x20
CHAIN_DEQUANT_WORKLOAD = 0x21
CHAIN_COMPRESS_WORKLOAD = 0x22
CHAIN_CHECKSUM_WORKLOAD = 0x23

#: one quantize_stream output row: 64 int8 lanes (as f32) + 1 fp32 scale
QUANT_ROW = HDR_BYTES + 1
#: one framed ingress-chain slot: RoCE header bytes + quant payload
FRAME_ROW = HDR_BYTES + QUANT_ROW
#: one parsed frame row: 4 meta words + the untouched quant payload
PARSED_ROW = 4 + QUANT_ROW
#: one checksum row: [checksum mod 2^24, input row width]
CSUM_ROW = 2


def _next_pow2(n: int) -> int:
    return 1 << max(3, (int(n) - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _stream_parser(bp: int):
    """Jitted parser per pow2 packet bucket: steady-state streaming must
    not re-trace the Pallas call per burst (the compute-side analogue of
    the descriptor executor's shape-bucket cache). Callers bucket
    ``bp`` to a power of two, so the unbounded cache stays a handful of
    entries."""
    return jax.jit(functools.partial(parse_packets, block_p=bp,
                                     interpret=ops._interpret()))


def _parse_bucketed(pkts: np.ndarray) -> np.ndarray:
    """Pad a packet batch to its pow2 bucket, parse with the cached
    jitted program, slice the live rows (row-wise kernel: padding never
    changes a live row's bytes)."""
    n = pkts.shape[0]
    bp = _next_pow2(n)
    padded = np.zeros((bp, HDR_BYTES), np.uint8)
    padded[:n] = pkts
    return _stream_parser(bp)(jnp.asarray(padded, jnp.uint8))[:n]


#: an unaligned matmul dim up to this size is one whole block; a larger
#: one is zero-padded to whole 128-blocks, so no block outgrows VMEM
#: (whole blocks of at most 512^2 f32 words hold 7 MiB, double-buffered)
MAX_WHOLE_DIM = 512


def _mm_blocks(m: int, k: int, n: int):
    """``((padded, block), ...)`` for m, k, n: MXU-aligned 128 blocks for
    aligned dims and for unaligned dims above ``MAX_WHOLE_DIM`` (zero-
    padded to a multiple of 128); one whole block for an unaligned dim up
    to ``MAX_WHOLE_DIM`` (for k that keeps one K step, so the
    accumulation order — and hence the bytes — match ``ref_matmul``)."""
    def one(d: int):
        if d % 128 == 0 or d > MAX_WHOLE_DIM:
            return -(-d // 128) * 128, 128
        return d, d
    return one(m), one(k), one(n)


@functools.lru_cache(maxsize=None)
def _mm_program(m: int, k: int, n: int):
    """Jitted (M,K)x(K,N) systolic program per shape, padded to its
    blocks (zero rows/cols add nothing to a live output)."""
    (mp, bm), (kp, bk), (np_, bn) = _mm_blocks(m, k, n)

    def run(x, y):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
        y = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
        z = systolic_mm(x, y, block_m=bm, block_n=bn, block_k=bk,
                        interpret=ops._interpret())
        return z[:m, :n]
    return jax.jit(run)


def lc_systolic_mm(ctx, remote_peer, rkey, a_addr, b_addr, out_addr,
                   m, k, n):
    """Offloaded (M,K)x(K,N) matmul: read A,B -> MXU systolic MM -> write C."""
    a_loc, b_loc = ctx.alloc(m * k), ctx.alloc(k * n)
    c_loc = ctx.alloc(m * n)
    ctx.read_remote(remote_peer, rkey, a_addr, a_loc, m * k)
    ctx.read_remote(remote_peer, rkey, b_addr, b_loc, k * n)
    ctx.commit(wait=True)
    if ctx.failed:
        raise RuntimeError(
            f"operand fetch failed: {ctx.failed[0].status.value}")
    x = jnp.asarray(ctx.load(a_loc, m * k).reshape(m, k))
    y = jnp.asarray(ctx.load(b_loc, k * n).reshape(k, n))
    z = _mm_program(m, k, n)(x, y)
    ctx.store(c_loc, np.asarray(z, np.float32).reshape(-1))
    ctx.write_remote(remote_peer, rkey, c_loc, out_addr, m * n)
    ctx.commit(wait=ctx.eager_writeback)
    return out_addr


def lc_packet_parser(ctx, remote_peer, rkey, pkts_addr, n_pkts, out_addr):
    """Offloaded RoCEv2 classifier: read headers -> parse -> write meta.

    Packets ride the float32 pool as byte values 0..255 (exact in fp32);
    the (n_pkts, 4) int32 metadata rows write back the same way (every
    field < 2^24, exact in fp32)."""
    nbytes = n_pkts * HDR_BYTES
    in_loc, out_loc = ctx.alloc(nbytes), ctx.alloc(n_pkts * 4)
    ctx.read_remote(remote_peer, rkey, pkts_addr, in_loc, nbytes)
    ctx.commit(wait=True)
    if ctx.failed:
        raise RuntimeError(
            f"packet fetch failed: {ctx.failed[0].status.value}")
    pkts = ctx.load(in_loc, nbytes).reshape(n_pkts, HDR_BYTES)
    meta = _parse_bucketed(pkts)
    ctx.store(out_loc, np.asarray(meta, np.float32).reshape(-1))
    ctx.write_remote(remote_peer, rkey, out_loc, out_addr, n_pkts * 4)
    ctx.commit(wait=ctx.eager_writeback)
    return out_addr


def _gather_spans(ctx, ring_peer, ring_rkey, in_loc, spans,
                  unit: int) -> int:
    """Post the loopback READ gather of a sub-burst's ring spans into
    contiguous scratch (``unit`` pool words per slot). Returns total
    words gathered. The WQEs are POSTED only — the caller arms them
    deferred so the whole service round shares one descriptor table."""
    off = 0
    for addr, cnt in spans:
        if cnt:
            ctx.read_remote(ring_peer, ring_rkey, addr, in_loc + off,
                            cnt * unit)
            off += cnt * unit
    return off


def _scatter_rows(ctx, ring_base, out_peer, out_rkey, out_base, out_loc,
                  spans, row: int, unit: int = HDR_BYTES) -> None:
    """RDMA-WRITE each span's result rows to the handler's class-mirrored
    output ring at the matching slot indices (``row`` words per output
    slot). ``unit`` is the INPUT region's row width — spans address the
    source ring, so slot recovery divides by the source row size
    (``HDR_BYTES`` for the classic packet-ring handlers; a chain stage
    passes its own ``in_row``)."""
    off = 0
    for addr, cnt in spans:
        if cnt:
            slot0 = (addr - ring_base) // unit
            ctx.write_remote(out_peer, out_rkey, out_loc + off,
                             out_base + slot0 * row, cnt * row)
            off += cnt * row


def lc_packet_parser_stream(ctx, ring_peer, ring_rkey, ring_base,
                            out_peer, out_rkey, out_base, spans):
    """Streaming ``packet_parser`` handler (§IV-D): parse one sub-burst.

    A GENERATOR kernel — the two phases around the ``yield`` are what the
    pipelined service loop overlaps across invocations (and, in a
    dispatch group, across HANDLERS):

      fetch    — gather the sub-burst's contiguous ring spans into
                 contiguous scratch with loopback READ WQEs on the
                 kernel's own QP, armed deferred (one descriptor table
                 per flush, shared with the other handlers' gathers and
                 any armed host traffic);
      compute  — parse the headers (the same Pallas kernel as the
                 ControlMsg path, padded to a pow2 packet bucket so
                 steady-state bursts reuse a handful of programs) and
                 RDMA-WRITE each span's metadata rows to the meta ring
                 on ``out_peer`` at the matching slot indices.

    Byte-contract: identical rows to ``lc_packet_parser`` (and the
    ``kernels/ref.py`` oracle) for the same header bytes.
    """
    n_pkts = sum(cnt for _, cnt in spans)
    nbytes = n_pkts * HDR_BYTES
    in_loc = ctx.alloc(nbytes)
    meta_loc = ctx.alloc(n_pkts * 4)
    _gather_spans(ctx, ring_peer, ring_rkey, in_loc, spans, HDR_BYTES)
    ctx.commit(wait=False)       # armed: the service loop flushes
    yield                        # ...and resumes once the gather lands
    if ctx.failed:
        raise RuntimeError(
            f"ring gather failed: {ctx.failed[0].status.value}")
    pkts = ctx.load(in_loc, nbytes).reshape(n_pkts, HDR_BYTES)
    meta = _parse_bucketed(pkts)
    ctx.store(meta_loc, np.asarray(meta, np.float32).reshape(-1))
    _scatter_rows(ctx, ring_base, out_peer, out_rkey, out_base, meta_loc,
                  spans, 4)
    ctx.commit(wait=ctx.eager_writeback)
    return out_base


@functools.lru_cache(maxsize=None)
def _stream_quant(bp: int):
    """Jitted per pow2 row bucket like ``_stream_parser``: steady-state
    bulk streaming must not re-trace the Pallas quantizer per burst."""
    return jax.jit(functools.partial(quantize_stream, chunk=HDR_BYTES,
                                     interpret=ops._interpret()))


def _quant_bucketed(x: np.ndarray):
    """Pad a (n, 64) payload batch to its pow2 row bucket, quantize with
    the cached jitted program, slice the live rows (each row quantizes
    independently with its own scale, so padding never changes a live
    row's bytes)."""
    n = x.shape[0]
    bp = _next_pow2(n)
    padded = np.zeros((bp, HDR_BYTES), np.float32)
    padded[:n] = x
    q, s = _stream_quant(bp)(jnp.asarray(padded))
    return q[:n], s[:n]


@functools.lru_cache(maxsize=None)
def _stream_dequant(bp: int):
    """Jitted inverse of ``_stream_quant`` per pow2 row bucket: the KV
    serving client decompresses every fetched page with it, so
    steady-state decode must not re-trace the Pallas call per fetch."""
    return jax.jit(functools.partial(dequantize_stream,
                                     interpret=ops._interpret()))


def _dequant_bucketed(q: np.ndarray, s: np.ndarray) -> np.ndarray:
    """Pad (n, 64) int8 rows + their scales to the pow2 row bucket,
    dequantize with the cached jitted program, slice the live rows
    (row-wise kernel: padding never changes a live row's bytes)."""
    n = q.shape[0]
    bp = _next_pow2(n)
    qpad = np.zeros((bp, HDR_BYTES), np.int8)
    qpad[:n] = q
    spad = np.ones((bp, 1), np.float32)
    spad[:n] = s
    out = _stream_dequant(bp)(jnp.asarray(qpad), jnp.asarray(spad))
    return np.asarray(out[:n])


def lc_quantize_stream(ctx, ring_peer, ring_rkey, ring_base,
                       out_peer, out_rkey, out_base, spans):
    """Streaming bulk-class handler: int8-quantize one sub-burst's
    payload slots in flight (the Streaming Compute block's gradient-
    compression role — ``quantize_stream`` per 64-lane slot chunk).

    Same generator shape as the parser handler (fetch → ``yield`` →
    compute/write-back); each slot's output row is its 64 int8 values
    (as f32 — exact) followed by its fp32 max-abs scale, written to the
    class-mirrored output ring at the matching slot index.

    Byte-contract: identical to ``ref.ref_quantize`` row-wise on the
    same slot bytes.
    """
    n_slots = sum(cnt for _, cnt in spans)
    nwords = n_slots * HDR_BYTES
    in_loc = ctx.alloc(nwords)
    out_loc = ctx.alloc(n_slots * QUANT_ROW)
    _gather_spans(ctx, ring_peer, ring_rkey, in_loc, spans, HDR_BYTES)
    ctx.commit(wait=False)       # armed: the service loop flushes
    yield                        # ...and resumes once the gather lands
    if ctx.failed:
        raise RuntimeError(
            f"ring gather failed: {ctx.failed[0].status.value}")
    x = ctx.load(in_loc, nwords).reshape(n_slots, HDR_BYTES)
    q, s = _quant_bucketed(x)
    rows = np.concatenate([np.asarray(q, np.float32),
                           np.asarray(s, np.float32)], axis=1)
    ctx.store(out_loc, rows.reshape(-1))
    _scatter_rows(ctx, ring_base, out_peer, out_rkey, out_base, out_loc,
                  spans, QUANT_ROW)
    ctx.commit(wait=ctx.eager_writeback)
    return out_base


# --------------------------------------------------------------- chains
@dataclass(frozen=True)
class ChainStageSpec:
    """Row geometry one chain-stage kernel publishes so
    ``StreamDispatcher.register_chain`` can compose and validate a
    pipeline: the stage's fixed output row width, plus what it demands
    of its input rows (``fixed_in_row`` pins the width exactly,
    ``min_in_row`` lower-bounds it — e.g. the dequantize stage consumes
    the trailing ``QUANT_ROW`` words of however wide a row the upstream
    emits)."""
    out_row: int
    fixed_in_row: Optional[int] = None
    min_in_row: int = 1


def _checksum_rows(rows: np.ndarray) -> np.ndarray:
    """(n, w) f32 rows → (n, 2) f32 [checksum, w] integrity rows.

    The checksum is the position-weighted sum of each word's raw 32-bit
    pattern, ``sum((i+1) * bits_i) mod 2^24`` in int64 — mod 2^24 keeps
    the value exactly representable in the f32 pool, and hashing the bit
    patterns (not the float values) makes the stamp sensitive to every
    payload bit, including NaN payloads and signed zeros."""
    rows = np.ascontiguousarray(np.asarray(rows, np.float32))
    bits = rows.view(np.uint32).astype(np.int64)
    w = np.arange(1, rows.shape[1] + 1, dtype=np.int64)
    csum = (bits * w).sum(axis=1) % (1 << 24)
    out = np.stack([csum, np.full_like(csum, rows.shape[1])], axis=1)
    return out.astype(np.float32)


def _chain_stage_kernel(compute, out_row: int):
    """Build one chain-stage generator kernel from a row-batch compute
    fn. The generator shape matches the stream handlers — gather the
    input spans (``in_row`` words per slot) with loopback READs armed
    deferred, ``yield`` for the shared flush, then compute and
    RDMA-WRITE slot-mirrored ``out_row``-word rows — so a stage pipelines
    through ``_service_grouped`` exactly like any handler, and its
    write-back region is the next stage's fetch source."""
    def stage(ctx, in_peer, in_rkey, in_base, out_peer, out_rkey,
              out_base, spans, in_row):
        n = sum(cnt for _, cnt in spans)
        nwords = n * in_row
        in_loc = ctx.alloc(nwords)
        out_loc = ctx.alloc(n * out_row)
        _gather_spans(ctx, in_peer, in_rkey, in_loc, spans, in_row)
        ctx.commit(wait=False)   # armed: the service loop flushes
        yield                    # ...and resumes once the gather lands
        if ctx.failed:
            raise RuntimeError(
                f"chain stage gather failed: {ctx.failed[0].status.value}")
        rows = ctx.load(in_loc, nwords).reshape(n, in_row)
        out = compute(rows)
        ctx.store(out_loc, np.asarray(out, np.float32).reshape(-1))
        _scatter_rows(ctx, in_base, out_peer, out_rkey, out_base,
                      out_loc, spans, out_row, unit=in_row)
        ctx.commit(wait=ctx.eager_writeback)
        return out_base
    return stage


def _parse_frame_rows(rows: np.ndarray) -> np.ndarray:
    """(n, FRAME_ROW) framed slots → (n, PARSED_ROW) [meta ‖ payload]:
    the header bytes run through the SAME cached Pallas parser as the
    stream handler; the quant payload passes through untouched for the
    next stage."""
    hdrs = np.asarray(rows[:, :HDR_BYTES], np.uint8)
    meta = np.asarray(_parse_bucketed(hdrs), np.float32)
    return np.concatenate([meta, np.asarray(rows[:, HDR_BYTES:],
                                            np.float32)], axis=1)


def _dequant_trailing_rows(rows: np.ndarray) -> np.ndarray:
    """(n, ≥QUANT_ROW) rows → (n, 64) f32: dequantize the TRAILING
    ``QUANT_ROW`` words (64 int8 lanes as f32 + the fp32 scale) with the
    cached ``_stream_dequant`` programs — leading words (e.g. the parse
    stage's meta) are pass-by metadata this stage ignores."""
    q = np.asarray(rows[:, -QUANT_ROW:-1], np.float32).astype(np.int8)
    s = np.asarray(rows[:, -1:], np.float32)
    return _dequant_bucketed(q, s)


def _compress_rows(rows: np.ndarray) -> np.ndarray:
    """(n, 64) f32 rows → (n, QUANT_ROW) [q ‖ scale] rows via the cached
    ``_stream_quant`` programs — byte parity with
    ``kops.compress(x, chunk=64)`` row-wise."""
    q, s = _quant_bucketed(np.asarray(rows, np.float32))
    return np.concatenate([np.asarray(q, np.float32),
                           np.asarray(s, np.float32)], axis=1)


#: workload id → (stage kernel compute fn, spec) of every chain-capable
#: kernel ``register_chain_kernels`` installs.
CHAIN_STAGES = {
    CHAIN_PARSE_WORKLOAD: (
        "chain_parse", _parse_frame_rows,
        ChainStageSpec(out_row=PARSED_ROW, fixed_in_row=FRAME_ROW)),
    CHAIN_DEQUANT_WORKLOAD: (
        "chain_dequant", _dequant_trailing_rows,
        ChainStageSpec(out_row=HDR_BYTES, min_in_row=QUANT_ROW)),
    CHAIN_COMPRESS_WORKLOAD: (
        "chain_compress", _compress_rows,
        ChainStageSpec(out_row=QUANT_ROW, fixed_in_row=HDR_BYTES)),
    CHAIN_CHECKSUM_WORKLOAD: (
        "chain_checksum", _checksum_rows,
        ChainStageSpec(out_row=CSUM_ROW)),
}


def register_chain_kernels(block, weight: int = 1):
    """Register the chain-capable stage kernels on a block, attaching
    each one's ``ChainStageSpec`` so ``register_chain`` can validate
    pipeline composition. Idempotent per block for already-registered
    ids is NOT supported (same contract as ``register``)."""
    for wid, (name, compute, spec) in CHAIN_STAGES.items():
        k = block.register(wid, _chain_stage_kernel(compute, spec.out_row),
                           name, weight=weight)
        k.stage_spec = spec
    return block


def register_default_kernels(block, weight: int = 1):
    """Register the paper's example offload kernels on a block (the two
    ControlMsg kernels plus the dispatch plane's stream handler mix)."""
    block.register(MM_WORKLOAD, lc_systolic_mm, "systolic_mm",
                   weight=weight)
    block.register(PARSER_WORKLOAD, lc_packet_parser, "packet_parser",
                   weight=weight)
    block.register(STREAM_PARSER_WORKLOAD, lc_packet_parser_stream,
                   "packet_parser_stream", weight=weight)
    block.register(STREAM_QUANT_WORKLOAD, lc_quantize_stream,
                   "quantize_stream", weight=weight)
    return block
