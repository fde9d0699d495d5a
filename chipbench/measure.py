#!/usr/bin/env python3
"""Measure a cell for its bounds: sets of runs with the same seeds, each run a
process of its own, then traced runs; for each end-to-end metric each set's
median and spread.

    python chipbench/measure.py --workload <name> --seeds 1 2 3 \\
        --out DIR [--sets 2] [--traced 4 5] [--seconds <s>]

A spread is the distance between the first and the third quartile, as
``statistics.quantiles(values, n=4)`` gives them, over the median. Every
run's record is appended to ``DIR/measure_<workload>.jsonl``; the first run
that fails or is not correct ends the measurement.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values: list[float]) -> float:
    """The spread with the run farthest from the median left out, where that
    narrows it."""
    med = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return min(spread(values), spread(rest)) if len(rest) > 1 else \
        spread(values)


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t = time.time()
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)], capture_output=True, text=True, cwd=ROOT)
    lines = p.stdout.strip().splitlines()
    rec = {"seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.time() - t,
           "log": [line for line in lines[:-1]
                   if line.startswith(("compiles", "setup_s",
                                       "window spans"))]}
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["stderr"] = p.stderr[-3000:]
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--out", required=True,
                    help="directory for the records of the runs")
    args = ap.parse_args()
    seconds = args.seconds or json.load(
        open(os.path.join(ROOT, "BENCHMARK.json")))["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, f"measure_{args.workload}.jsonl"), "a")
    runs = [(f"set{k}", s, 0) for k in range(args.sets) for s in args.seeds]
    runs += [("traced", s, 1) for s in args.traced]
    sets: dict[str, dict[str, list]] = {}
    for tag, seed, trace in runs:
        rec = dict(one(args.workload, seed, seconds, trace), tag=tag)
        log.write(json.dumps(rec) + "\n")
        log.flush()
        res = rec.get("result", {})
        values = {k: v["value"] for k, v in res.get("metrics", {}).items()}
        print(tag, seed, "rc", rec["rc"], "wall %.1f" % rec["wall_s"],
              "correct", res.get("correct"), values, rec["log"], flush=True)
        if rec["rc"] or not res.get("correct"):
            print(rec.get("stderr", json.dumps(res.get("checks"))),
                  flush=True)
            return 1
        if not trace:
            for k, v in values.items():
                sets.setdefault(tag, {}).setdefault(k, []).append(v)
    for tag, metrics in sets.items():
        for k, vs in metrics.items():
            if len(vs) > 1:
                print(f"{tag} {k}: median {statistics.median(vs)!r} "
                      f"spread {spread(vs)!r} trimmed "
                      f"{trimmed_spread(vs)!r} over {len(vs)} runs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
