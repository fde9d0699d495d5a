#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU chips of this machine.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic and metrics are named in
``BENCHMARK.json`` at the root of the checkout. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end to end with ``--trace 0``, per layer with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last the ``checks``
that decided ``correct``, each with its value and limit (also the last
lines of standard error). Without the cell's TPU chips it exits 2 and prints
no result.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from chipbench import bench
    try:
        result = bench.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), START)
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    bench.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
