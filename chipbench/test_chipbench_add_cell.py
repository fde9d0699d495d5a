"""A cell is added with files alone: a copy of the benchmark gains a loop
kind of its own (WRITEs, built on the verbs loop) with a fault and a control
of its own, a traffic mix, its tiny sizes and its entries in
``BENCHMARK.json``, and no file that was under ``chipbench/`` changes. The
copy keeps to the contract, and its new cell runs, correct when sound and
not correct under its fault or its control."""
import hashlib
import json
import os
import shutil
import time

from chipbench import bench, faults
from chipbench.conftest import make_tiny_root
from chipbench.test_chipbench_layout import check_cell, check_contract

CELL = "verbs_write_64B_b50"
KIND = "verbs_write_loop"

WRITE_LOOP = '''"""One-sided WRITEs from peer 1 to peer 0 on the verbs loop."""
import os

from chipbench import bench
from chipbench.faults import both_transports

verbs = bench.loop_module("verbs_closed_loop", os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


class VerbsWriteLoop(verbs.LOOP):
    def setup(self):
        if self.traffic["opcode"] != "WRITE":
            raise ValueError("a WRITE loop runs WRITEs")
        super().setup()


def reversed_writes():
    """Each WRITE delivered the wrong way, from peer 0 to peer 1."""
    def wrap(orig):
        def run(self, plan):
            return orig(self, [(k, d, s, da, sa, n)
                               for k, s, d, sa, da, n in plan])
        return run
    return both_transports("execute_batch", wrap)


def headless_writes():
    """Control: each WRITE delivers all of its words but the first."""
    def wrap(orig):
        def run(self, plan):
            return orig(self, [(k, s, d, sa + 1, da + 1, max(0, n - 1))
                               for k, s, d, sa, da, n in plan])
        return run
    return both_transports("execute_batch", wrap)


LOOP = VerbsWriteLoop
FAULTS = ("reversed_writes",)
CONTROL = "headless_writes"
'''


def digests(root):
    out = {}
    for d, _, names in os.walk(os.path.join(root, "chipbench")):
        for n in names:
            path = os.path.join(d, n)
            with open(path, "rb") as f:
                out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def add_write_cell(root):
    """The new cell's files and entries, as a later change would add
    them."""
    cb = os.path.join(root, "chipbench")
    write(os.path.join(cb, "loops", KIND + ".py"), WRITE_LOOP)
    write(os.path.join(cb, "traffic", "write_64B_b50.json"), json.dumps({
        "loop": KIND, "opcode": "WRITE", "message_words": 16, "batch": 50,
        "doorbells_in_flight": 2, "tape_batches": 4096,
        "warmup_batches": 16, "why": "random 64 B WRITEs, never adjacent"}))
    write(os.path.join(cb, "tiny", "traffic", "write_64B_b50.json"),
          json.dumps({"tape_batches": 8, "warmup_batches": 2}))
    spec = bench.spec(root)
    spec["workloads"].append({
        "name": CELL, "config": "rdma_perftest_1chip",
        "traffic": "write_64B_b50", "chips": 1,
        "why": "closed loop, 1 QP, random 64 B WRITEs, 50 per doorbell"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("msg_rate", "engine.host_us_per_wqe.msg_rate"):
            m["workloads"].append(CELL)
    write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec, indent=2))


def run(root, trace=False):
    return bench.run_cell(CELL, 2 ** 31 + 17, 0.3, trace, time.perf_counter(),
                          require_chip=False, root=root, log=lambda *a: None)


def test_a_cell_with_a_loop_of_its_own_is_added_with_files_alone(tmp_path):
    copy = str(tmp_path / "copy")
    shutil.copytree(os.path.join(bench.ROOT, "chipbench"),
                    os.path.join(copy, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bench.ROOT, "BENCHMARK.json"), copy)
    before = digests(copy)
    add_write_cell(copy)

    check_contract(copy)
    for w in bench.spec(copy)["workloads"]:
        check_cell(w["name"], copy)

    tiny = make_tiny_root(str(tmp_path / "tiny"), src=copy)
    sound = run(tiny, trace=True)
    assert sound["correct"], sound["checks"]
    assert sound["metrics"]["engine.host_us_per_wqe.msg_rate"]["value"] > 0
    assert set(run(tiny)["metrics"]) == {"msg_rate", "setup_s"}
    for plant in ("reversed_writes", "headless_writes"):
        with faults.plant(plant, KIND, tiny):
            res = run(tiny)
        assert not res["correct"], (plant, res["checks"])

    after = digests(copy)
    assert {p: after.get(p) for p in before} == before
