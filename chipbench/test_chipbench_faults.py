"""The harness at a tiny size on the CPU, with the timed path broken
underneath: each fault a cell can have, and its control, turn ``correct``
false; the sound path keeps it true."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import bench, faults

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = bench.spec()
#: every cell, each on one CPU device at its tiny size
CELLS = [w["name"] for w in SPEC["workloads"]]
#: faults of the exchange between chips, which no cell on one device has:
#: ``test_four_peer_ici_faults_turn_correct_false`` plants them
ICI_ONLY = ("no_exchange",)


def run(root, workload, seed=2 ** 31 + 7, seconds=0.3, trace=False):
    return bench.run_cell(workload, seed, seconds, trace, time.perf_counter(),
                          require_chip=False, root=root, log=lambda *a: None)


def loop_of(workload, root=bench.ROOT):
    """The loop kind of a cell, as its traffic names it, and the kind's
    file."""
    kind = bench.resolve(workload, root).traffic["loop"]
    return kind, bench.loop_module(kind, root)


def cases():
    for w in CELLS:
        mod = loop_of(w)[1]
        for name in (*mod.FAULTS, mod.CONTROL):
            if name not in ICI_ONLY:
                yield w, name


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_reports_its_metrics(tiny_root, workload):
    res = run(tiny_root, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    names = {m["name"] for m in bench.resolve(workload, tiny_root).end_to_end}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload,plant", list(cases()))
def test_fault_or_control_turns_correct_false(tiny_root, workload, plant):
    with faults.plant(plant, loop_of(workload)[0], tiny_root):
        res = run(tiny_root, workload)
    assert not res["correct"], (plant, res["checks"])


def test_traced_run_reports_host_span_metrics(tiny_root):
    res = run(tiny_root, "verbs_read_64B_b50", trace=True)
    assert res["correct"]
    assert res["metrics"]["engine.host_us_per_wqe.msg_rate"]["value"] > 0
    assert res["metrics"]["engine.host_us_per_wqe.msg_rate"]["unit"] == "us"


def test_allreduce_checks_a_seeded_sample_copied_into_buffers_of_setup(
        tiny_root):
    """The window copies the sampled results into buffers made in set-up
    and keeps no array of the program's."""
    from chipbench.cells import Spans
    r = bench.resolve("allreduce_25MiB_ring", tiny_root)
    loop = loop_of("allreduce_25MiB_ring", tiny_root)[1].LOOP(
        r.config, r.traffic, 2 ** 31 + 9, 0.3, Spans())
    loop.setup()
    kept = loop.kept
    assert kept.shape == (r.traffic["check_sample"], 4, r.config["bucket_words"])
    loop.window()
    assert loop.kept is kept
    assert loop.checked == [j for j in loop.sampled if j < loop.attempted]
    assert any(n == "bench.keep" for n, _, _ in loop.spans.events)
    loop.release()
    checks = loop.check()
    assert checks["none_checked"][0] == 0
    assert checks["sum_error"][0] < 1e-6


def test_four_peer_ici_faults_turn_correct_false(tmp_path):
    """Each four-chip cell over ICITransport on four virtual CPU devices,
    sound, under each fault its loop lists (the exchange between chips left
    out among them) and under its control."""
    from chipbench.conftest import make_tiny_root
    root = make_tiny_root(str(tmp_path), ici=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    four = [w["name"] for w in SPEC["workloads"] if w["chips"] == 4]
    assert four
    for workload in four:
        mod = loop_of(workload, root)[1]
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "control.py"), "--cpu",
             "--root", root, "--workload", workload,
             "--seconds", "0.3", "--seeds", "3",
             "--plant", "sound", "faults", "control"],
            env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        got = json.loads(out.stdout.strip().splitlines()[-1])
        assert got.pop("sound")["correct"] == [True]
        assert set(got) == {*mod.FAULTS, mod.CONTROL}
        for plant, readings in got.items():
            assert readings["correct"] == [False], (workload, plant)
