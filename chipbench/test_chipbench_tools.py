"""The command-line tools on the CPU: ``run.py`` refuses to run without the
cell's chips, ``measure.py`` stops at the first run that fails, and the
probes run at a tiny size."""
import json
import os
import statistics
import subprocess
import sys

import pytest

from chipbench import measure

HERE = os.path.dirname(os.path.abspath(__file__))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def tool(*args, timeout=240):
    return subprocess.run([sys.executable, *args], env=ENV,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("values,want", [
    ([1.0, 2.0, 3.0, 4.0, 5.0], 1.0),
    ([10.0, 10.0, 10.0, 10.0], 0.0),
    ([99.0, 100.0, 101.0, 100.5, 99.5, 100.0], None),
])
def test_spread_is_the_quartile_distance_over_the_median(values, want):
    q1, _, q3 = statistics.quantiles(values, n=4)
    got = measure.spread(values)
    assert got == pytest.approx((q3 - q1) / statistics.median(values))
    if want is not None:
        assert got == pytest.approx(want)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_run_without_the_chips_exits_2_and_prints_no_result(trace):
    out = tool(os.path.join(HERE, "run.py"), "--workload",
               "verbs_read_64B_b50", "--seed", str(2 ** 33 + 1),
               "--seconds", "1", "--trace", trace)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "needs 1 TPU chip" in out.stderr


def test_measure_stops_at_the_first_run_that_fails(tmp_path):
    out = tool(os.path.join(HERE, "measure.py"), "--workload",
               "verbs_read_64B_b50", "--seeds", "1", "2", "--out",
               str(tmp_path))
    assert out.returncode == 1
    recs = [json.loads(line) for line in
            open(tmp_path / "measure_verbs_read_64B_b50.jsonl")]
    assert len(recs) == 1 and recs[0]["rc"] == 2 and "result" not in recs[0]


def test_slot_probe_runs_at_a_tiny_size():
    out = tool(os.path.join(HERE, "probe.py"), "slots", "--cpu", "--pools",
               "10", "--chunks", "16", "--slot-counts", "4")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "pool 2^10 chunk 16 slots 4:" in out.stdout


def test_kv_probe_fetches_every_sequence_at_a_tiny_size():
    out = tool(os.path.join(HERE, "probe.py"), "kv", "--cpu", "--pool-log2",
               "14", "--page-elems", "16", "--tokens", "4", "8", "--reps", "1")
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert len(lines) == 2
    assert all(line.endswith("1 done") for line in lines)
