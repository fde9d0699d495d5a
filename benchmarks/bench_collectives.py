"""Gradient-bucket collective benchmark: the training stream on the
shared engine (ISSUE 8 tentpole claims, CI-gated via
``BENCH_collectives.json``).

Sections:

* ``ring``    — ring all-reduce parity vs the host-sum oracle, measured
  wire words vs the α–β ideal (2(n-1)/n of the vector per peer — ratio
  exactly 1.0), and warm-compile counts across repeated steps.
* ``rd``      — recursive-doubling parity on a non-pow2 peer count
  (fold/broadcast path).
* ``overlap`` — pipelined buckets (``defer=True`` doorbells): flushes
  serving >1 in-flight bucket vs total, plus the serial-depth flush
  count for the amortization ratio.
* ``fairness``— two equal-weight serving tenants stream READs while the
  collective reduces buckets on a DRR engine with a flush budget: their
  service Jain must be exactly 1.0 (training cannot starve serving).
* ``chaos``   — 10% seeded drop: byte parity through go-back-N
  retransmission with zero new compiles.
* ``model``   — ``simulate_collective`` α–β predictions (serial vs
  pipelined round times) for the same shapes.
"""
import json

import numpy as np

from repro.core.rdma.cost_model import jain_fairness_index
from repro.core.rdma.engine import RDMAEngine
from repro.core.rdma.reliability import FaultInjector
from repro.core.rdma.simulator import simulate_collective
from repro.core.rdma.verbs import Opcode, WQE
from repro.train.collectives import RDMACollective, ideal_wire_words

N_PEERS = 4
WORDS = 1024          # per-bucket vector words (pow2: chunk = 256)


def _shards(rng, n: int, words: int):
    """Integer-valued f32 shards: exact under any reduction order."""
    return [rng.integers(-8, 9, words).astype(np.float32)
            for _ in range(n)]


def run_ring(steps: int):
    rng = np.random.default_rng(0)
    eng = RDMAEngine(n_peers=N_PEERS, pool_size=1 << 13)
    coll = RDMACollective(eng, N_PEERS, algorithm="ring")
    coll.all_reduce(_shards(rng, N_PEERS, WORDS))        # warm-up
    c0 = eng.stats["transport"]["compiles"]
    q0 = eng.stats["transport"]["qdma_compiles"]
    w0 = coll.stats["wire_bytes"]
    parity = True
    for _ in range(steps):
        shards = _shards(rng, N_PEERS, WORDS)
        got = coll.all_reduce(shards)
        want = np.sum(shards, axis=0)
        parity &= all(np.array_equal(got[p][:WORDS], want)
                      for p in range(N_PEERS))
    wire = (coll.stats["wire_bytes"] - w0) // 4     # f32 pool words
    ideal = steps * ideal_wire_words("ring", N_PEERS, WORDS)
    return {
        "steps": steps,
        "parity": bool(parity),
        "wire_words": wire,
        "ideal_wire_words": ideal,
        "wire_ratio": wire / ideal,
        "warm_descriptor_compiles": eng.stats["transport"]["compiles"]
        - c0,
        "warm_qdma_compiles": eng.stats["transport"]["qdma_compiles"]
        - q0,
    }


def run_rd(steps: int):
    """Recursive doubling on n=5: extras fold in and broadcast out."""
    rng = np.random.default_rng(1)
    n = 5
    eng = RDMAEngine(n_peers=n, pool_size=1 << 12)
    coll = RDMACollective(eng, n, algorithm="rd")
    coll.all_reduce(_shards(rng, n, 320))                # warm-up
    c0 = eng.stats["transport"]["compiles"]
    parity = True
    for _ in range(steps):
        shards = _shards(rng, n, 320)
        got = coll.all_reduce(shards)
        want = np.sum(shards, axis=0)
        parity &= all(np.array_equal(got[p][:320], want)
                      for p in range(n))
    return {
        "n_peers": n,
        "parity": bool(parity),
        "warm_descriptor_compiles": eng.stats["transport"]["compiles"]
        - c0,
    }


def run_overlap(n_buckets: int):
    """Pipelined vs serial bucket schedule: same buckets, depth 2 vs 1."""
    rng = np.random.default_rng(2)

    def _go(depth: int):
        eng = RDMAEngine(n_peers=2, pool_size=1 << 15)
        coll = RDMACollective(eng, 2, pipeline_depth=depth)
        buckets = [_shards(rng, 2, WORDS) for _ in range(n_buckets)]
        got = coll.all_reduce_buckets(buckets)
        for b, shards in enumerate(buckets):
            want = np.sum(shards, axis=0)
            assert np.array_equal(got[b][0][:WORDS], want)
        return coll.stats

    serial = _go(1)
    piped = _go(2)
    return {
        "n_buckets": n_buckets,
        "serial_flushes": serial["flushes"],
        "pipelined_flushes": piped["flushes"],
        "overlapped_flushes": piped["overlapped_flushes"],
        "overlap_fraction": piped["overlapped_flushes"]
        / piped["flushes"],
        "flush_ratio_serial_over_pipelined": serial["flushes"]
        / piped["flushes"],
    }


def run_fairness(backlog: int):
    """Serving tenants under a streaming collective on one DRR engine."""
    eng = RDMAEngine(n_peers=2, pool_size=1 << 14, scheduler="drr",
                     flush_budget=6)
    hi = eng.pool_size - 512
    eng.register_mr(0, hi, 256)
    src = eng.register_mr(1, hi, 256)
    tenants = [eng.create_qp(0, 1, weight=2) for _ in range(2)]
    for i in range(backlog):
        for qp in tenants:
            eng.post_send(qp, WQE(Opcode.READ, qp.qp_num,
                                  wr_id=0x53450000 + 2 * i + qp.qp_num,
                                  local_addr=hi, remote_addr=src.base,
                                  length=4, rkey=src.rkey))
            eng.ring_sq_doorbell(qp, defer=True)
    rng = np.random.default_rng(3)
    coll = RDMACollective(eng, 2, weight=2, pipeline_depth=2)
    buckets = [_shards(rng, 2, 256) for _ in range(3)]
    got = coll.all_reduce_buckets(buckets)
    for b, shards in enumerate(buckets):
        assert np.array_equal(got[b][0][:256], np.sum(shards, axis=0))
    served = [eng.stats["qp_service"].get(q.qp_num, 0) for q in tenants]
    return {
        "serving_backlog": backlog,
        "serving_service": served,
        "serving_jain": jain_fairness_index(served),
        "collective_flushes": coll.stats["flushes"],
        "interleaved_batches": eng.stats["transport"].get(
            "interleaved_batches", 0),
    }


def run_chaos(steps: int):
    """10% seeded drop: retransmitted gradient chunks stay byte-exact
    and ride the warmed shape buckets."""
    rng = np.random.default_rng(4)
    n = 3
    eng = RDMAEngine(n_peers=n, pool_size=1 << 12)
    eng.install_fault_injector(FaultInjector(11, drop=0.10))
    coll = RDMACollective(eng, n)
    coll.all_reduce(_shards(rng, n, 192))                # warm-up
    c0 = eng.stats["transport"]["compiles"]
    q0 = eng.stats["transport"]["qdma_compiles"]
    parity = True
    for _ in range(steps):
        shards = _shards(rng, n, 192)
        got = coll.all_reduce(shards)
        want = np.sum(shards, axis=0)
        parity &= all(np.array_equal(got[p][:192], want)
                      for p in range(n))
    rel = eng.stats.get("reliability", {})
    return {
        "parity_10pct_drop": bool(parity),
        "retransmits": rel.get("retransmits", 0),
        "warm_descriptor_compiles": eng.stats["transport"]["compiles"]
        - c0,
        "warm_qdma_compiles": eng.stats["transport"]["qdma_compiles"]
        - q0,
    }


def run_model():
    ring = simulate_collective(4 << 20, N_PEERS, algorithm="ring",
                               n_buckets=4, pipeline_depth=2)
    rd = simulate_collective(4 << 20, N_PEERS, algorithm="rd")
    return {
        "ring_pipelined_us": ring["pipelined_us"],
        "ring_serial_us": ring["serial_us"],
        "pipeline_speedup": ring["pipeline_speedup"],
        "rd_rounds": rd["rounds"],
        "rd_over_ring_wire": rd["wire_bytes"] / ring["wire_bytes"],
    }


def run(verbose: bool = True, smoke: bool = False, out_json: str = ""):
    steps = 3 if smoke else 8
    ring = run_ring(steps)
    rd = run_rd(max(2, steps // 2))
    overlap = run_overlap(4 if smoke else 8)
    fair = run_fairness(24 if smoke else 64)
    chaos = run_chaos(2 if smoke else 5)
    model = run_model()
    rec = {
        "workload": {"n_peers": N_PEERS, "bucket_words": WORDS,
                     "steps": steps},
        "ring": ring,
        "rd": rd,
        "overlap": overlap,
        "fairness": fair,
        "chaos": chaos,
        "model": model,
        # compile-count gate: pow2 chunk buckets mean steady-state
        # collective steps can never compile, smoke or full
        "warm_descriptor_compiles": (
            ring["warm_descriptor_compiles"]
            + rd["warm_descriptor_compiles"]
            + chaos["warm_descriptor_compiles"]),
        "warm_qdma_compiles": (ring["warm_qdma_compiles"]
                               + chaos["warm_qdma_compiles"]),
    }
    if verbose:
        print(f"coll_ring_parity,0.0,parity={ring['parity']},"
              f"wire_ratio={ring['wire_ratio']:.3f}x")
        print(f"coll_rd_parity,0.0,parity={rd['parity']}"
              f"(n={rd['n_peers']})")
        print(f"coll_overlap,0.0,"
              f"frac={overlap['overlap_fraction']:.2f}"
              f"(flushes={overlap['pipelined_flushes']}"
              f"/{overlap['serial_flushes']}serial)")
        print(f"coll_fairness,0.0,jain={fair['serving_jain']:.4f}"
              f"(service={fair['serving_service']})")
        print(f"coll_chaos,0.0,parity={chaos['parity_10pct_drop']}"
              f"(retx={chaos['retransmits']})")
        print(f"coll_model,{model['ring_pipelined_us']:.1f},"
              f"speedup={model['pipeline_speedup']:.3f}x")

    # -- acceptance criteria (the PR's hard claims) ----------------------
    assert ring["parity"] and rd["parity"], "parity vs oracle broke"
    assert abs(ring["wire_ratio"] - 1.0) < 1e-9, ring["wire_ratio"]
    assert rec["warm_descriptor_compiles"] == 0, (
        "steady-state collective steps must not compile: "
        f"{rec['warm_descriptor_compiles']}")
    assert rec["warm_qdma_compiles"] == 0
    assert overlap["overlap_fraction"] > 0, "buckets never overlapped"
    assert overlap["pipelined_flushes"] < overlap["serial_flushes"]
    assert fair["serving_jain"] == 1.0, fair["serving_service"]
    assert min(fair["serving_service"]) > 0, "serving starved"
    assert chaos["parity_10pct_drop"], "lossy fabric corrupted grads"
    assert chaos["retransmits"] > 0, "drop profile never fired"

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
    run(out_json="BENCH_collectives.json")
