#!/usr/bin/env python3
"""Probes of the descriptor executor's cost, for what no cell can run yet.

    python chipbench/probe.py slots [--pools 20 22 24 26]
    python chipbench/probe.py kv [--pool-log2 28] [--tokens 16 64 ...]

``slots``: ``_exec_descriptors_local`` with an all-zero descriptor table at
each pool size (2^k words per peer), chunk and slot count, three dispatches
each: the executor's cost per slot. ``kv``: published sequences of KV pages
(``--page-elems`` values, ``--pages-per-token`` pages per token; 4096 and
4.5 are Qwen2.5-3B's widths in vLLM blocks), each fetched alone through
``RemoteKVClient`` with every stage timed: the cost per page READ. Each
prints one line per reading. ``--cpu`` runs on the CPU at whatever size is
given, as the tests do.
"""
import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def slots(pools: list[int], chunks: list[int], counts: list[int]) -> None:
    import jax.numpy as jnp
    from repro.core.rdma.transport import _exec_descriptors_local
    for lg in pools:
        pool = jnp.zeros((2, 1 << lg), jnp.float32)
        for chunk in chunks:
            for n in counts:
                desc = jnp.zeros((n, 5), jnp.int32)
                # each dispatch takes the pool the last one made, as the
                # transport does, so an executor may donate the pool
                pool = _exec_descriptors_local(pool, desc, chunk)
                pool.block_until_ready()
                t = time.perf_counter()
                for _ in range(3):
                    pool = _exec_descriptors_local(pool, desc, chunk)
                pool.block_until_ready()
                dt = (time.perf_counter() - t) / 3
                print(f"pool 2^{lg} chunk {chunk} slots {n}: {dt * 1e3:.3f} "
                      f"ms per dispatch, {dt / n * 1e6:.1f} us per slot",
                      flush=True)


def kv(pool_log2: int, page_elems: int, pages_per_token: float,
       tokens: list[int], reps: int) -> None:
    import jax.numpy as jnp
    from repro.core.rdma import RDMAEngine
    from repro.serve.kv_cache import PagedKVPool, RemoteKVClient
    words = 1 << pool_log2
    eng = RDMAEngine(n_peers=2, pool_size=words)
    pool = PagedKVPool(eng, 0, page_elems=page_elems,
                       max_pages=words // page_elems, dtype=jnp.bfloat16)
    pages = [int(t * pages_per_token) for t in tokens]
    # page i of every sequence in turn: no sequence's pages adjacent
    for i in range(max(pages)):
        for k, n in enumerate(pages):
            if i < n:
                pool.append_page(k)
    client = RemoteKVClient(eng, 1, pool, staging_size=words // 8)
    tenant = client.register_tenant("decode")
    for rep in range(reps):
        for k, t in enumerate(tokens):
            t0 = time.perf_counter()
            client.fetch_sequence(tenant, k, defer=True)
            t1 = time.perf_counter()
            eng.flush_doorbells()
            t2 = time.perf_counter()
            eng.transport.pool.block_until_ready()
            t3 = time.perf_counter()
            done = client.advance(tenant)
            t4 = time.perf_counter()
            print(f"rep {rep} tokens {t} pages {pages[k]} post {t1 - t0:.4f} "
                  f"flush {t2 - t1:.4f} device {t3 - t2:.4f} advance "
                  f"{t4 - t3:.4f} total {t4 - t0:.4f} s, {len(done)} done",
                  flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("probe", choices=("slots", "kv"))
    ap.add_argument("--pools", type=int, nargs="+", default=[20, 22, 24, 26])
    ap.add_argument("--chunks", type=int, nargs="+", default=[256, 4096])
    ap.add_argument("--slot-counts", type=int, nargs="+", default=[8, 64])
    ap.add_argument("--pool-log2", type=int, default=28)
    ap.add_argument("--page-elems", type=int, default=4096)
    ap.add_argument("--pages-per-token", type=float, default=4.5)
    ap.add_argument("--tokens", type=int, nargs="+",
                    default=[16, 64, 128, 256, 512, 1024])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    import jax
    if not args.cpu:
        if jax.devices()[0].platform != "tpu":
            print("probe: no TPU chip here", file=sys.stderr)
            return 2
        from chipbench import bench
        bench.enable_compile_cache()
    if args.probe == "slots":
        slots(args.pools, args.chunks, args.slot_counts)
    else:
        kv(args.pool_log2, args.page_elems, args.pages_per_token,
           args.tokens, args.reps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
