# Service-chain dataplane (the PR-9 tentpole claim): a MatchTable entry
# that names an ordered PIPELINE of lookaside kernels, served two ways —
#
#   chained  ONE framed RX ring whose default action is the
#            parse→dequantize Chain; per grouped service pass stage N's
#            write-back rows are stage N+1's fetch source, and every
#            stage gather/write-back shares the engine's shape-bucketed
#            descriptor tables (dataflow_msgs in the per-chain ledger);
#   staged   the same traffic drained one stage at a time — a fresh
#            single-stage chain per kernel, each paying its own flushes.
#
# Hard claims (asserted here, gated in CI via scale-invariant keys):
# every stage's output rows are byte-identical to composing the stage
# computes directly (stage_parity); the egress compress→checksum
# production chain (GradEgressChain) is byte-identical to
# kops.compress(chunk=64) with verifiable checksums (egress_parity,
# checksums_ok); the chained drive takes fewer flushes than the staged
# serial sum (flush_ratio_staged_over_chained > 1); the measured replay
# of the warm-up cycle compiles ZERO new descriptor/staging programs;
# and under 10% seeded wire drop the chain output stays byte-identical
# (chaos.parity_10pct_drop) with zero fresh compiles after warm-up.
# Wall clocks are recorded as data, never gated (noisy VM).
import json
import time

import numpy as np

POOL = 1 << 15
DATA_PEER, LC_PEER = 1, 0
RING_DEPTH = 16
BURST = 4
PIPE_DEPTH = 4
CYCLES = 8
SMOKE_CYCLES = 3


def _frames(n, seed=0):
    """n framed ingress slots: 64 header bytes ‖ 65-word quant payload."""
    from repro.core.streaming import make_roce_header

    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        hdr = make_roce_header(4, 100 + i, is_rdma=False, dport=9000)
        payload = np.concatenate([
            rng.integers(-127, 128, 64).astype(np.float32),
            np.asarray([rng.uniform(0.01, 2.0)], np.float32)])
        out.append(np.concatenate([hdr.astype(np.float32), payload]))
    return np.stack(out)


def _ingress_setup(eng=None, depth=RING_DEPTH, burst=BURST):
    from repro.core.lookaside import LookasideBlock
    from repro.core.rdma import RDMAEngine
    from repro.core.streaming import (Chain, MatchTable, RXRing,
                                      StreamDispatcher)
    from repro.kernels.lc_offload import (CHAIN_DEQUANT_WORKLOAD,
                                          CHAIN_PARSE_WORKLOAD, FRAME_ROW,
                                          HDR_BYTES, PARSED_ROW,
                                          register_chain_kernels)

    eng = eng or RDMAEngine(n_peers=2, pool_size=POOL)
    blk = LookasideBlock(eng, peer=LC_PEER, scratch_base=POOL // 2,
                         scratch_size=POOL // 4, eager_writeback=False,
                         pipeline_depth=PIPE_DEPTH)
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
    return eng, ring, disp, (s1, s2)


def _drive_and_verify(eng, ring, disp, frames, bases, depth=RING_DEPTH):
    """Window-by-window drive; after each service pass compare BOTH
    stage output rings against the composed direct-invoke oracles.
    Returns byte-parity over every packet (slots are checked before the
    next window reuses them)."""
    from repro.kernels.lc_offload import (HDR_BYTES, PARSED_ROW,
                                          _dequant_trailing_rows,
                                          _parse_frame_rows)

    s1, s2 = bases
    ok = True
    i = 0
    while i < len(frames):
        n = min(depth, len(frames) - i)
        win = frames[i:i + n]
        for f in win:
            assert ring.push(f)          # untagged: the default chain owns it
        disp.service()
        o1 = np.asarray(_parse_frame_rows(win))
        o2 = np.asarray(_dequant_trailing_rows(o1))
        g1 = eng.read_buffer(DATA_PEER, s1, depth * PARSED_ROW
                             ).reshape(depth, PARSED_ROW)
        g2 = eng.read_buffer(DATA_PEER, s2, depth * HDR_BYTES
                             ).reshape(depth, HDR_BYTES)
        ok = (ok and np.array_equal(g1[:n], o1)
              and np.array_equal(g2[:n], o2))
        i += n
    return ok


def run_chained(frames, warm_frames):
    """Warm-up cycle, then the measured replay with per-window stage
    parity checks and flush/compile accounting."""
    from repro.core.rdma.transport import (descriptor_cache_size,
                                           staging_cache_size)

    eng, ring, disp, bases = _ingress_setup()
    _drive_and_verify(eng, ring, disp, warm_frames, bases)
    d0, s0 = descriptor_cache_size(), staging_cache_size()
    f0 = eng.stats["flushes"]
    t0 = time.perf_counter()
    parity = _drive_and_verify(eng, ring, disp, frames, bases)
    wall = time.perf_counter() - t0
    led = dict(eng.stats["dispatch"]["chains"]["ingress"])
    return {
        "wall_s": wall,
        "pkts_per_s": len(frames) / wall,
        "flushes": eng.stats["flushes"] - f0,
        "warm_descriptor_compiles": descriptor_cache_size() - d0,
        "warm_qdma_compiles": staging_cache_size() - s0,
        "stage_parity": bool(parity),
        "ledger": led,
        "completion": led["completed_pkts"] / max(1, led["pkts"]),
    }


def run_staged(frames):
    """The no-pipeline layout: the SAME traffic drained one stage at a
    time, each stage a fresh single-stage chain paying its own flushes
    (stage 2 consumes stage 1's oracle rows, as a serial drain would)."""
    from repro.core.lookaside import LookasideBlock
    from repro.core.rdma import RDMAEngine
    from repro.core.streaming import (Chain, MatchTable, RXRing,
                                      StreamDispatcher)
    from repro.kernels.lc_offload import (CHAIN_DEQUANT_WORKLOAD,
                                          CHAIN_PARSE_WORKLOAD, FRAME_ROW,
                                          HDR_BYTES, PARSED_ROW,
                                          _parse_frame_rows,
                                          register_chain_kernels)

    def single(stage_wid, rows, slot_bytes, out_row):
        eng = RDMAEngine(n_peers=2, pool_size=POOL)
        blk = LookasideBlock(eng, peer=LC_PEER, scratch_base=POOL // 2,
                             scratch_size=POOL // 4, eager_writeback=False,
                             pipeline_depth=PIPE_DEPTH)
        register_chain_kernels(blk)
        ring = RXRing(eng, peer=LC_PEER, base=0, depth=RING_DEPTH,
                      slot_bytes=slot_bytes)
        chain = Chain((stage_wid,))
        disp = StreamDispatcher(blk, ring, MatchTable(default=chain),
                                burst=BURST)
        base = slot_bytes * RING_DEPTH + 64
        mr = eng.register_mr(DATA_PEER, base, out_row * RING_DEPTH)
        disp.register_chain(chain, DATA_PEER, mr.rkey, [base])
        f0 = eng.stats["flushes"]
        i = 0
        while i < len(rows):
            n = min(RING_DEPTH, len(rows) - i)
            for r in rows[i:i + n]:
                assert ring.push(r)
            disp.service()
            i += n
        return eng.stats["flushes"] - f0

    o1 = np.asarray(_parse_frame_rows(frames))
    t0 = time.perf_counter()
    flushes = (single(CHAIN_PARSE_WORKLOAD, frames, FRAME_ROW, PARSED_ROW)
               + single(CHAIN_DEQUANT_WORKLOAD, o1, PARSED_ROW, HDR_BYTES))
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "pkts_per_s": len(frames) / wall,
            "flushes": flushes}


def run_egress(n_elems=1280):
    """Production compress→checksum egress chain vs kops.compress."""
    import jax.numpy as jnp
    from repro.core.rdma import RDMAEngine
    from repro.core.streaming import GradEgressChain
    from repro.kernels import ops as kops

    eng = RDMAEngine(n_peers=2, pool_size=POOL)
    ch = GradEgressChain(eng, data_peer=DATA_PEER, ring_base=1024,
                         out_base=4096, lc_peer=LC_PEER,
                         scratch_base=POOL // 2, scratch_size=POOL // 4,
                         depth=16, burst=8)
    flat = np.random.default_rng(3).normal(size=n_elems).astype(np.float32)
    t0 = time.perf_counter()
    q, s, csum, _ = ch.compress(flat, np.zeros(n_elems, np.float32))
    wall = time.perf_counter() - t0
    kq, ks, _ = kops.compress(jnp.asarray(flat), chunk=64)
    parity = bool(np.array_equal(q, np.asarray(kq))
                  and np.array_equal(s, np.asarray(ks)))
    led = dict(eng.stats["dispatch"]["chains"]["grad_egress"])
    return {
        "wall_s": wall,
        "rows_per_s": q.shape[0] / wall,
        "egress_parity": parity,
        "checksums_ok": bool(GradEgressChain.verify_checksums(q, s, csum)),
        "ledger": led,
        "completion": led["completed_pkts"] / max(1, led["pkts"]),
    }


def run_chaos(frames, warm_frames):
    """The chained ingress drive on a 10%-drop 3%-corrupt seeded wire
    (PR-6 reliability layer): parity must hold via retransmission, and
    the replay after warm-up must compile nothing new."""
    from repro.core.rdma import (FaultInjector, RDMAEngine,
                                 ReliabilityConfig)
    from repro.core.rdma.transport import descriptor_cache_size

    eng = RDMAEngine(n_peers=2, pool_size=POOL, scheduler="drr",
                     flush_budget=8)
    eng.install_fault_injector(FaultInjector(3, drop=0.10, corrupt=0.03),
                               ReliabilityConfig(retry_cnt=16))
    eng, ring, disp, bases = _ingress_setup(eng=eng)
    _drive_and_verify(eng, ring, disp, warm_frames, bases)
    d0 = descriptor_cache_size()
    parity = _drive_and_verify(eng, ring, disp, frames, bases)
    led = dict(eng.stats["dispatch"]["chains"]["ingress"])
    return {
        "parity_10pct_drop": bool(parity),
        "warm_descriptor_compiles": descriptor_cache_size() - d0,
        "retransmits": eng.stats["reliability"]["retransmits"],
        "completion": led["completed_pkts"] / max(1, led["pkts"]),
    }


def run(verbose: bool = True, smoke: bool = False, out_json: str = ""):
    from repro.core.rdma.simulator import simulate_chain
    from repro.kernels.lc_offload import FRAME_ROW, HDR_BYTES, PARSED_ROW

    cycles = SMOKE_CYCLES if smoke else CYCLES
    warm = _frames(RING_DEPTH, seed=1)
    frames = _frames(cycles * RING_DEPTH, seed=2)

    chained = run_chained(frames, warm)
    staged = run_staged(frames)
    egress = run_egress()
    chaos = run_chaos(frames, warm)
    # the model is analytic and instant: evaluate at the FULL workload
    # size regardless of smoke so its gated keys stay scale-invariant
    model = simulate_chain(CYCLES * RING_DEPTH,
                           rows=(FRAME_ROW, PARSED_ROW, HDR_BYTES),
                           burst=BURST, pipeline_depth=PIPE_DEPTH)

    rec = {
        "workload": {"n_pkts": len(frames), "stages": 2, "burst": BURST,
                     "ring_depth": RING_DEPTH,
                     "pipeline_depth": PIPE_DEPTH, "smoke": smoke},
        "chained": chained, "staged": staged, "egress": egress,
        "chaos": chaos,
        "stage_parity": chained["stage_parity"],
        "egress_parity": egress["egress_parity"],
        "checksums_ok": egress["checksums_ok"],
        "warm_descriptor_compiles": chained["warm_descriptor_compiles"],
        "warm_qdma_compiles": chained["warm_qdma_compiles"],
        "flush_ratio_staged_over_chained": (staged["flushes"]
                                            / max(1, chained["flushes"])),
        "chain_completion": chained["completion"],
        "model": model,
    }
    if verbose:
        print(f"chains_chained,{chained['wall_s'] * 1e6:.1f},"
              f"{chained['pkts_per_s']:.0f}pkts/s,"
              f"flushes={chained['flushes']},"
              f"dataflow={chained['ledger']['dataflow_msgs']}")
        print(f"chains_staged,{staged['wall_s'] * 1e6:.1f},"
              f"{staged['pkts_per_s']:.0f}pkts/s,"
              f"flushes={staged['flushes']}")
        print(f"chains_flush_ratio,0.0,"
              f"{rec['flush_ratio_staged_over_chained']:.2f}x")
        print(f"chains_egress,{egress['wall_s'] * 1e6:.1f},"
              f"{egress['rows_per_s']:.0f}rows/s,"
              f"parity={egress['egress_parity']},"
              f"checksums={egress['checksums_ok']}")
        print(f"chains_warm_compiles,0.0,"
              f"desc={rec['warm_descriptor_compiles']}"
              f"+qdma={rec['warm_qdma_compiles']}")
        print(f"chains_chaos,0.0,parity={chaos['parity_10pct_drop']},"
              f"retx={chaos['retransmits']}")

    # -- acceptance criteria (the PR's hard claims) ----------------------
    assert chained["stage_parity"], (
        "chain stage output diverged from the composed direct oracles")
    assert egress["egress_parity"] and egress["checksums_ok"], (
        "egress chain wire bytes diverged from kops.compress")
    assert rec["warm_descriptor_compiles"] == 0, (
        "steady-state chain streaming recompiled descriptor programs: "
        f"{rec['warm_descriptor_compiles']}")
    assert rec["warm_qdma_compiles"] == 0, (
        f"chain ring pushes recompiled staging: {rec['warm_qdma_compiles']}")
    assert chained["flushes"] < staged["flushes"], (
        "the chain must share inter-stage flushes: "
        f"{chained['flushes']} chained vs {staged['flushes']} staged")
    assert chained["completion"] == 1.0 and chaos["completion"] == 1.0
    assert chaos["parity_10pct_drop"], "chaos parity broke"
    assert chaos["retransmits"] > 0, "chaos injected nothing"
    assert model["flush_ratio"] > 1.0
    assert model["chained_speedup_vs_staged"] > 1.0

    if out_json:
        with open(out_json, "w") as f:
            json.dump(rec, f, indent=2, default=float)
            f.write("\n")
        if verbose:
            print(f"# wrote {out_json}")
    return rec


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    run(out_json="BENCH_chains.json")
