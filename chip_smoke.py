#!/usr/bin/env python3
"""Bring-up smoke test of the RDMA engine's main path on TPU chips.

    python chip_smoke.py [--seed N]             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4 [--seed N]   # four chips: ring all-reduce

One chip runs the engine with two peers on one device (``LocalTransport``
loopback) over a pool of 2^26 f32 words per peer, through the calls a user
of the engine makes:

  (a) verbs      one doorbell batch of READ and WRITE WQEs, 64 B to 1 MiB,
                 checked bit for bit against a numpy copy of the pool;
  (b) KV handoff tinyllama-1.1b at full width: prefill 8 requests of 256
                 tokens, publish their caches as 4096-element pages, fetch
                 them back over one-sided READs and decode 16 tokens, which
                 must equal decoding on the local caches; then a few pages
                 through a compressed pool, checked against the quantize
                 oracles of ``repro.kernels.ref``;
  (c) lookaside  a 1024^3 ``lc_systolic_mm`` offload through a
                 ``LookasideBlock``, checked against a float64 matmul;
  (d) ingress    a 4096-header burst of 64-byte headers through
                 ``TrafficRouter``, checked against ``ref_parse_fields``.

``--chips 4`` runs only the ``RDMACollective`` ring all-reduce of one
25 MiB bucket per peer over ``ICITransport`` on four chips, checked against
a numpy sum and an XLA ``psum`` on the same mesh.

Every input comes from ``--seed``. Each phase prints its checks and its
wall time on one line; that time is set-up time, compilation included,
and never a speed. A failed check raises, so the process exits non-zero
and the closing JSON line is not printed. Without a TPU the script exits
with code 2 before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: pool words per peer on one chip (256 MiB of f32 per peer)
POOL_WORDS = 1 << 26
#: WQE sizes of phase (a), in f32 words: 64 B .. 1 MiB
VERB_WORDS = tuple(16 << i for i in range(15))
#: KV page: 16 tokens x 4 KV heads x head_dim 64
PAGE_ELEMS = 4096
#: pages sent through the compressed pool in phase (b)
COMPRESSED_PAGES = 8
#: phase (c) tolerance on max |C - A@B| / (sqrt(K) rms(A) rms(B)). f32 at
#: default precision on the MXU multiplies in one bf16 pass: 1.3e-2 on a
#: TPU v5e at 1024^3, so 2^-5 leaves margin while a wrong tile or operand
#: gives ~1.
MM_TOL = 2.0 ** -5
#: DDP's default bucket_cap_mb, in f32 words
BUCKET_WORDS = 25 * (1 << 20) // 4


class SmokeFailure(RuntimeError):
    """A check of the smoke test did not hold."""


def check(ok: bool, what: str) -> str:
    if not ok:
        raise SmokeFailure(what)
    return what


def report(phase: str, t0: float, checks) -> None:
    print(f"[{phase}] " + "; ".join(checks)
          + f" | set-up time incl. compilation {time.perf_counter() - t0:.3f} s"
          " (not a speed)", flush=True)


def on_cpu(fn, *args):
    """Run a reference on the host CPU backend, apart from the chip."""
    with jax.default_device(jax.devices("cpu")[0]):
        return jax.tree.map(np.asarray, fn(*args))


def has_kernel(jitted, *args) -> bool:
    """True if ``jitted`` compiles ``args`` to a Mosaic kernel call."""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def probe_pool(device, chunk: int = 1 << 18):
    """Largest pool (words per peer, two peers) whose descriptor and
    staging executors fit the device, from their compiled memory analysis."""
    from repro.core.rdma.transport import (MIN_SLOT_BUCKET,
                                           _exec_descriptors_local,
                                           _exec_staging)
    limit = device.memory_stats()["bytes_limit"]
    largest = 0
    for lg in range(26, 30):
        pool = jax.ShapeDtypeStruct((2, 1 << lg), jnp.float32)
        programs = (
            _exec_descriptors_local.lower(
                pool, jax.ShapeDtypeStruct((MIN_SLOT_BUCKET, 5), jnp.int32),
                chunk=chunk),
            _exec_staging.lower(
                pool, jax.ShapeDtypeStruct((chunk,), jnp.float32),
                jax.ShapeDtypeStruct((3,), jnp.int32), chunk=chunk))
        need = []
        for lowered in programs:
            try:
                ma = lowered.compile().memory_analysis()
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                need.append(None)       # the compiler refused: no fit
                continue
            need.append(ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes)
        fits = all(n is not None and n <= limit for n in need)
        print(f"  pool 2 x 2^{lg} f32 words: executor args+out+temp "
              + ", ".join("refused" if n is None else f"{n / 2**30:.3f} GiB"
                          for n in need)
              + f" vs HBM limit {limit / 2**30:.3f} GiB -> "
              + ("fits" if fits else "does not fit"), flush=True)
        if fits:
            largest = 1 << lg
    return largest


def phase_verbs(eng, rng, sizes=VERB_WORDS) -> None:
    """(a) one doorbell batch of READ + WRITE WQEs vs a numpy copy."""
    from repro.core.rdma import Opcode, WQE
    from repro.core.rdma.verbs import CQEStatus
    t0 = time.perf_counter()
    total = sum(sizes)
    span = 2 * total
    for peer in (0, 1):
        eng.write_buffer(peer, 0, rng.standard_normal(span, np.float32))
    before = [eng.read_buffer(peer, 0, span) for peer in (0, 1)]
    want = [b.copy() for b in before]
    mr = eng.register_mr(0, 0, span)
    qp = eng.create_qp(1, 0)
    off = 0
    for i, n in enumerate(sizes):
        # READ peer 0 [off, off+n) into peer 1 at the same offset; WRITE
        # peer 1 [total+off, ...) into peer 0 at the same offset
        eng.post_send(qp, WQE(Opcode.READ, qp.qp_num, 2 * i, local_addr=off,
                              remote_addr=off, length=n, rkey=mr.rkey))
        want[1][off:off + n] = before[0][off:off + n]
        w = total + off
        eng.post_send(qp, WQE(Opcode.WRITE, qp.qp_num, 2 * i + 1,
                              local_addr=w, remote_addr=w, length=n,
                              rkey=mr.rkey))
        want[0][w:w + n] = before[1][w:w + n]
        off += n
    d0 = eng.transport.dispatch_count
    eng.ring_sq_doorbell(qp)
    cqes = eng.poll_cq(qp, max_entries=4 * len(sizes))
    after = [eng.read_buffer(peer, 0, span) for peer in (0, 1)]
    n_wqe = 2 * len(sizes)
    report("a verbs", t0, [
        f"{n_wqe} WQEs ({len(sizes)} READ + {len(sizes)} WRITE, "
        f"{4 * sizes[0]} B..{4 * sizes[-1]} B) in one doorbell",
        check(eng.transport.dispatch_count - d0 == 1,
              "executed as 1 descriptor-table dispatch"),
        check(len(cqes) == n_wqe
              and all(c.status is CQEStatus.SUCCESS for c in cqes)
              and [c.wr_id for c in cqes] == list(range(n_wqe)),
              f"{n_wqe}/{n_wqe} CQEs SUCCESS in posting order"),
        check(all(np.array_equal(a.view(np.uint32), b.view(np.uint32))
                  for a, b in zip(after, want)),
              f"both peers' {span}-word regions bit-identical to the "
              "numpy copy")])


def _request(caches, r: int):
    """Request ``r``'s slice of a batched (layer, batch, ...) cache tree."""
    return jax.tree.map(lambda x: x[:, r:r + 1] if x.ndim > 1 else x, caches)


def _batch(per_request):
    """Inverse of ``_request`` over all requests."""
    return jax.tree.map(
        lambda *xs: (jnp.concatenate(xs, axis=1) if xs[0].ndim > 1
                     else xs[0]), *per_request)


def _same_bits(a, b) -> bool:
    pairs = [(np.asarray(x), np.asarray(y))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return all(x.dtype == y.dtype and x.shape == y.shape
               and x.tobytes() == y.tobytes() for x, y in pairs)


def tie_flips(x: np.ndarray, q: np.ndarray, s: np.ndarray) -> int:
    """Count values whose int8 code differs from numpy's correctly rounded
    ``round(x / s)``; raise if any difference is not a one-step flip at a
    half-integer quotient. Only the division may differ between backends:
    a TPU's f32 division can be 1 ulp off, which moves ``round`` across
    a .5 tie."""
    x, s = x.astype(np.float32), s.astype(np.float32)
    want = np.clip(np.rint(x / s), -127, 127)
    diff = q.astype(np.float32) != want
    quot = np.abs(x.astype(np.float64) / s.astype(np.float64))
    ulp = np.spacing(quot.astype(np.float32)).astype(np.float64)
    at_tie = np.abs(quot - np.floor(quot) - 0.5) <= 2 * ulp
    check(np.all(np.abs(q.astype(np.float32) - want)[diff] == 1)
          and np.all(at_tie[diff]),
          "int8 codes differ from numpy's rounding only at .5 ties")
    return int(diff.sum())


def phase_kv(eng, rng, seed: int, arch: str = "tinyllama-1.1b",
             n_req: int = 8, prompt_len: int = 256, gen_len: int = 16,
             page_elems: int = PAGE_ELEMS,
             compressed_pages: int = COMPRESSED_PAGES) -> None:
    """(b) prefill -> publish -> one-sided READ fetch -> decode."""
    from repro.configs.registry import get_config
    from repro.core.streaming.classifier import TrafficClass, TrafficRouter
    from repro.kernels import ref
    from repro.kernels.lc_offload import (_next_pow2, _stream_dequant,
                                          _stream_quant)
    from repro.kernels.quantize_stream import INV_QMAX
    from repro.models import init_caches, init_params
    from repro.serve import decode_step, prefill_step
    from repro.serve.kv_cache import (PAGE_CHUNK, PagedKVPool,
                                      RemoteKVClient, flatten_cache_leaves,
                                      unflatten_cache_leaves)
    t0 = time.perf_counter()
    cfg = get_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(seed), jnp.bfloat16)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (n_req, prompt_len)), jnp.int32)
    caches = init_caches(cfg, n_req, prompt_len + gen_len, jnp.bfloat16)
    prefill = jax.jit(lambda p, t, c: prefill_step(p, cfg, {"tokens": t}, c))
    step = jax.jit(lambda p, t, c, pos: decode_step(p, cfg, t, c, pos))
    logits, caches = prefill(params, prompts, caches)
    first = jnp.argmax(logits[:, -1], axis=-1)[:, None]

    def decode(c):
        tok, out = first, []
        for i in range(gen_len):
            logits, c = step(params, tok, c, jnp.int32(prompt_len + i))
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            out.append(tok)
        return np.asarray(jnp.concatenate(out, axis=1))

    # prefill node publishes each request's caches as pages of peer 0;
    # the decode node (peer 1) fetches them over one-sided READs
    per_req = [_request(caches, r) for r in range(n_req)]
    words = int(flatten_cache_leaves(per_req[0]).size)
    n_pages = -(-words // page_elems)
    pool = PagedKVPool(eng, 0, page_elems=page_elems,
                       max_pages=n_req * n_pages, dtype=jnp.bfloat16)
    router = TrafficRouter()
    client = RemoteKVClient(eng, 1, pool, router=router)
    tenant = client.register_tenant("decode")
    for r, c in enumerate(per_req):
        client.publish_caches(r, c)
    d0 = eng.transport.dispatch_count
    tickets = [client.fetch_sequence(tenant, r, defer=True)
               for r in range(n_req)]
    fetched = _batch([
        unflatten_cache_leaves(client.complete(t).reshape(-1), per_req[r])
        for r, t in enumerate(tickets)])
    fetch_dispatches = eng.transport.dispatch_count - d0
    local_tokens = decode(caches)
    remote_tokens = decode(fetched)
    led = eng.stats["kv_serve"]
    checks = [
        f"{arch}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_kv_heads} KV heads, vocab {cfg.vocab_size}, bf16",
        f"{n_req} requests x {prompt_len} prompt tokens -> "
        f"{n_req * n_pages} pages of {page_elems} elements "
        f"in {fetch_dispatches} fetch dispatch(es)",
        check(led["pages_fetched"] == n_req * n_pages
              and router.counters[TrafficClass.KV_PAGE]["count"]
              == n_req * n_pages,
              f"{led['pages_fetched']} pages fetched by one-sided READ"),
        check(_same_bits(fetched, caches),
              "fetched caches bit-identical to the local caches"),
        check(np.array_equal(local_tokens, remote_tokens),
              f"{gen_len} decoded tokens x {n_req} requests equal on "
              "fetched and local caches")]
    for r in range(n_req):
        pool.evict(r)

    # a few pages through a compressed pool, against the quantize oracles
    flat = flatten_cache_leaves(per_req[0])[:compressed_pages * page_elems]
    cpool = PagedKVPool(eng, 0, page_elems=page_elems,
                        max_pages=compressed_pages, dtype=jnp.bfloat16,
                        compressed=True)
    cclient = RemoteKVClient(eng, 1, cpool)
    ctenant = cclient.register_tenant("decode-compressed")
    cclient.publish_caches(0, flat)
    raw = np.stack([cpool.read_page_raw(p) for p in cpool.pages[0]])
    got = cclient.complete(cclient.fetch_sequence(ctenant, 0))
    # the kernels.ref oracles compiled by XLA for the same chip, whose f32
    # division the Mosaic kernel shares; numpy then checks the scales and
    # the dequantization exactly, and the int8 codes up to .5 ties
    x = flat.reshape(-1, PAGE_CHUNK)
    q, s = (np.asarray(a) for a in jax.jit(ref.ref_quantize)(x))
    want = np.asarray(jax.jit(ref.ref_dequantize)(q, s)).reshape(
        compressed_pages, -1)
    amax = np.max(np.abs(x), axis=1, keepdims=True)
    scales = np.where(amax == 0, np.float32(1), amax * np.float32(INV_QMAX))
    flips = tie_flips(x, q, s)
    chunks = page_elems // PAGE_CHUNK
    pairs = (q.astype(np.int64) + 128).reshape(compressed_pages, -1, 2)
    wire = np.concatenate(
        [s.reshape(compressed_pages, chunks),
         (pairs[..., 0] * 256 + pairs[..., 1]).astype(np.float32)], axis=1)
    bp = _next_pow2(chunks)
    rows = jax.ShapeDtypeStruct((bp, PAGE_CHUNK), jnp.float32)
    checks += [
        check(np.array_equal(raw.view(np.uint32), wire.view(np.uint32)),
              f"{compressed_pages} compressed pages' wire words equal "
              "ref_quantize packed"),
        check(np.array_equal(got.view(np.uint32), want.view(np.uint32)),
              "fetched compressed pages equal ref_dequantize(ref_quantize)"),
        check(np.array_equal(s, scales)
              and np.array_equal(got.reshape(-1, PAGE_CHUNK),
                                 q.astype(np.float32) * s),
              "scales and dequantized values equal numpy's exactly"),
        f"{flips} of {x.size} int8 codes differ from numpy's correctly "
        "rounded division, each by one step at a .5 tie",
        check(has_kernel(_stream_quant(bp), rows)
              and has_kernel(_stream_dequant(bp),
                             jax.ShapeDtypeStruct((bp, PAGE_CHUNK), jnp.int8),
                             jax.ShapeDtypeStruct((bp, 1), jnp.float32)),
              "quantize/dequantize programs hold tpu_custom_call")]
    cpool.evict(0)
    report("b kv handoff", t0, checks)


def phase_lookaside(eng, rng, m: int = 1024, k: int = 1024,
                    n: int = 1024) -> None:
    """(c) offloaded systolic matmul through a LookasideBlock."""
    from repro.core.lookaside import ControlMsg, LookasideBlock
    from repro.kernels.lc_offload import (MM_WORKLOAD, _mm_program,
                                          register_default_kernels)
    t0 = time.perf_counter()
    blk = LookasideBlock(eng, peer=1)
    register_default_kernels(blk)
    a = rng.standard_normal((m, k), np.float32)
    b = rng.standard_normal((k, n), np.float32)
    a_addr, b_addr, c_addr = 0, m * k, m * k + k * n
    mr = eng.register_mr(0, 0, c_addr + m * n)
    eng.write_buffer(0, a_addr, a.reshape(-1))
    eng.write_buffer(0, b_addr, b.reshape(-1))
    refused = blk.dispatch(ControlMsg(
        MM_WORKLOAD, (0, mr.rkey, a_addr, b_addr, c_addr, m, k, n), tag=1))
    status = blk.poll(MM_WORKLOAD)
    c = eng.read_buffer(0, c_addr, m * n).reshape(m, n)
    want = a.astype(np.float64) @ b.astype(np.float64)
    scale = (np.sqrt(k) * np.sqrt(np.mean(np.square(a, dtype=np.float64)))
             * np.sqrt(np.mean(np.square(b, dtype=np.float64))))
    err = float(np.max(np.abs(c - want)) / scale)
    report("c lookaside", t0, [
        check(refused is None and status is not None and status.ok,
              f"lc_systolic_mm {m}x{k}x{n} offload completed OK"),
        check(err <= MM_TOL,
              f"max |C - A@B| / (sqrt(K) rms(A) rms(B)) = {err:.3e} "
              f"<= {MM_TOL:.3e}"),
        check(has_kernel(_mm_program(m, k, n),
                         jax.ShapeDtypeStruct((m, k), jnp.float32),
                         jax.ShapeDtypeStruct((k, n), jnp.float32)),
              "matmul program holds tpu_custom_call")])


def make_headers(rng, n: int) -> np.ndarray:
    """(n, 64) uint8 headers: random bytes with IPv4/UDP/RoCEv2 fields set
    on most packets, so every parser branch is taken."""
    h = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    ipv4 = rng.random(n) < 0.95
    h[ipv4, 12], h[ipv4, 13] = 0x08, 0x00
    h[rng.random(n) < 0.95, 23] = 17
    roce = rng.random(n) < 0.6
    h[roce, 36], h[roce, 37] = 4791 >> 8, 4791 & 0xFF
    h[:, 42] = rng.integers(0, 24, n)
    return h


def phase_ingress(eng, rng, n: int = 4096) -> None:
    """(d) packet burst through TrafficRouter -> classify_packet_fields."""
    from repro.core.streaming.classifier import TrafficRouter
    from repro.core.streaming.classifier import classify_headers
    from repro.core.streaming.rx_ring import RXRing
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.kernels.packet_parser import HDR_BYTES
    t0 = time.perf_counter()
    headers = make_headers(rng, n)
    ring = RXRing(eng, peer=1, depth=n)
    router = TrafficRouter(rx_ring=ring)
    counts = router.ingest_packets(headers)
    want = on_cpu(ref.ref_parse_fields, headers)
    rdma = want[:, 0] == 1
    n_rdma = int(rdma.sum())
    streamed = headers[~rdma]
    landed = eng.read_buffer(1, ring.base, len(streamed) * HDR_BYTES)
    report("d ingress", t0, [
        f"{n} headers of {HDR_BYTES} B, {n_rdma} RoCEv2",
        check(np.array_equal(classify_headers(headers), want),
              "parsed fields equal ref_parse_fields exactly"),
        check(counts == {"rdma": n_rdma, "streamed": n - n_rdma,
                         "dropped": 0, "backpressure": 0, "shed": 0},
              f"router: {n_rdma} forwarded, {n - n_rdma} streamed"),
        check(np.array_equal(landed, streamed.astype(np.float32).ravel()),
              "streamed headers landed in the RX ring in order"),
        check(has_kernel(kops.classify_packet_fields,
                         jax.ShapeDtypeStruct((n, HDR_BYTES), jnp.uint8)),
              "parser program holds tpu_custom_call")])


def run_one_chip(seed: int) -> None:
    from repro.core.rdma import RDMAEngine
    from repro.core.rdma.transport import LocalTransport
    from repro.kernels import ops as kops
    rng = np.random.default_rng(seed)
    check(not kops._interpret(), "Pallas kernels compile to Mosaic")
    t0 = time.perf_counter()
    largest = probe_pool(jax.devices()[0])
    print(f"[pool] largest pool that fits one program: 2 x {largest} f32 "
          f"words ({2 * 4 * largest / 2**30:.3f} GiB) | set-up time "
          f"{time.perf_counter() - t0:.3f} s (not a speed)", flush=True)
    check(largest >= POOL_WORDS, f"the {POOL_WORDS}-word pool fits")
    eng = RDMAEngine(n_peers=2, pool_size=POOL_WORDS)
    print(f"[engine] transport {type(eng.transport).__name__}, 2 peers x "
          f"{POOL_WORDS} f32 words", flush=True)
    check(isinstance(eng.transport, LocalTransport),
          "two peers on one chip run on LocalTransport loopback")
    phase_verbs(eng, rng)
    phase_kv(eng, rng, seed)
    phase_lookaside(eng, rng)
    phase_ingress(eng, rng)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_four_chips(seed: int, words: int = BUCKET_WORDS) -> None:
    """Ring all-reduce of one bucket per peer over ICITransport, against a
    numpy sum and an XLA psum on the same mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.rdma import RDMAEngine
    from repro.core.rdma.transport import PEER_AXIS, ICITransport
    from repro.train.collectives import RDMACollective, ideal_wire_words
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    n = 4
    pool = 1 << max(16, (2 * words - 1).bit_length())   # data + scratch
    eng = RDMAEngine(n_peers=n, pool_size=pool)
    on_ici = check(isinstance(eng.transport, ICITransport),
                   f"transport ICITransport over {n} chips")
    shards = [rng.integers(-64, 64, words).astype(np.float32)
              for _ in range(n)]
    want = np.sum(shards, axis=0, dtype=np.float32)
    coll = RDMACollective(eng, algorithm="ring")
    got = coll.all_reduce(shards)
    mesh = eng.transport.mesh
    x = jax.device_put(np.stack(shards),
                       NamedSharding(mesh, P(PEER_AXIS, None)))
    psum = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, PEER_AXIS), mesh=mesh,
        in_specs=P(PEER_AXIS, None), out_specs=P(PEER_AXIS, None)))
    ref = np.asarray(psum(x))
    led = coll.stats
    report("ring all-reduce", t0, [
        on_ici,
        f"one bucket of {words} f32 words ({4 * words} B) per peer, "
        f"{led['rounds']} rounds",
        check(led["wire_bytes"] == 4 * ideal_wire_words("ring", n, words),
              f"{led['wire_bytes'] // 4} wire words = the ring's ideal"),
        check(all(np.array_equal(g, want) for g in got),
              "every peer's sum equals the numpy sum exactly"),
        check(all(np.array_equal(row, want) for row in ref),
              "and equals the XLA psum on the same mesh exactly")])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}, seed {args.seed}", flush=True)
    if args.chips == 4:
        run_four_chips(args.seed)
    else:
        run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
