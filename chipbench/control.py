#!/usr/bin/env python3
"""Readings for the limits of ``correct``: a cell's checks on several seeds,
sound, under its control, or under each fault it can have, in one process.

    python chipbench/control.py --workload <name> --seconds <s> \\
        --seeds 1 2 3 [--plant sound|control|faults|<name> ...]

Each run prints one line of readings; the last line is a JSON object
{plant: {check: [value per seed], "correct": [...]}}. ``--root`` and
``--cpu`` run a copy of the benchmark at a tiny size on the CPU, as the
tests do. The benchmark's own runs (``run.py``) never plant anything.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def plants(kind: str, asked: list[str], root: str) -> list[str]:
    """The plants asked for, with ``control`` and ``faults`` named as the
    file of the loop kind lists them."""
    from chipbench import bench
    loop = bench.loop_module(kind, root)
    out = []
    for a in asked:
        out += {"control": [loop.CONTROL],
                "faults": list(loop.FAULTS)}.get(a, [a])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--plant", nargs="+", default=["sound"])
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    from chipbench import bench, faults
    if not args.cpu:
        bench.enable_compile_cache()
    readings: dict = {}
    kind = bench.resolve(args.workload, args.root).traffic["loop"]
    for plant in plants(kind, args.plant, args.root):
        got = readings.setdefault(plant, {"correct": []})
        for seed in args.seeds:
            with (faults.plant(plant, kind, args.root) if plant != "sound"
                  else contextlib.nullcontext()):
                try:
                    res = bench.run_cell(
                        args.workload, seed, args.seconds, False,
                        time.perf_counter(), require_chip=not args.cpu,
                        root=args.root, log=lambda *a: None)
                except bench.NoChip as e:
                    print(f"control: {e}", file=sys.stderr)
                    return 2
                except Exception as e:      # a fault may crash the run
                    print(f"{plant} seed {seed}: crashed: {e!r}", flush=True)
                    got["correct"].append(False)
                    continue
            for k, c in res["checks"].items():
                got.setdefault(k, []).append(c["value"])
            got["correct"].append(res["correct"])
            print(f"{plant} seed {seed}: correct {res['correct']}; "
                  + "; ".join(f"{k} {c['value']} (limit {c['limit']})"
                              for k, c in res["checks"].items())
                  + "; " + json.dumps(res["metrics"]), flush=True)
    print(json.dumps(readings))
    return 0


if __name__ == "__main__":
    sys.exit(main())
