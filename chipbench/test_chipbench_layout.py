import os
import re
import shutil

import pytest

from chipbench import bench, cells, faults
from chipbench.conftest import tiny_files

ROOT = bench.ROOT
SPEC = bench.spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
#: keys that hold a width, which ``reduced`` may never name: sizes, head and
#: latent dimensions, ranks, expansion factors, experts per token. The
#: vocabulary is no width: a chip may hold its slice of it.
WIDTH = re.compile(r"(_dim|_rank|_size|_width|_channels)$|expan"
                   r"|experts_per_tok|top_?k")
#: the key by which a configuration names the model it runs: the model's
#: own ``config.json`` has it (Hugging Face's ``model_type``)
MODEL_KEY = "model_type"


def is_width(key: str) -> bool:
    return bool(WIDTH.search(key)) and not key.endswith("vocab_size")


def mfu_refused(metric: dict, spec: dict, configs: dict) -> bool:
    """A per-layer metric with ``mfu`` in its name where some cell it
    reports in runs a configuration that names no model: a share of the
    peak needs a model's operations."""
    if "mfu" not in metric["name"]:
        return False
    config_of = {w["name"]: w["config"] for w in spec["workloads"]}
    return any(MODEL_KEY not in configs[config_of[c]] for c in
               metric.get("workloads", config_of))


def check_cell(cell: str, root: str = ROOT) -> None:
    """A cell's files are found by name, its loop file defines what the
    harness and the tests read, and it has its tiny size."""
    r = bench.resolve(cell, root)
    assert r.config["name"] == r.cell["config"]
    assert r.config["chips"] == r.cell["chips"]
    loop = bench.loop_module(r.traffic["loop"], root)
    assert issubclass(loop.LOOP, cells.Loop)
    assert isinstance(loop.FAULTS, tuple) and isinstance(loop.CONTROL, str)
    for name in (*loop.FAULTS, loop.CONTROL):
        assert callable(getattr(loop, name, None)
                        or faults.SHARED.get(name)), name
    for path in tiny_files(r.cell):
        assert os.path.isfile(os.path.join(root, path)), \
            f"{cell} has no tiny file {path}"
    names = {m["name"] for m in r.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert r.per_layer
    for m in r.per_layer:
        assert callable(bench.reader(m, root))
        assert m["moves"] in names


def check_contract(root: str = ROOT) -> None:
    """``BENCHMARK.json`` at ``root`` keeps to the benchmark's contract."""
    spec = bench.spec(root)
    cell_names = [w["name"] for w in spec["workloads"]]
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["chipbench"]
    assert spec["command"][1].startswith("chipbench/")
    assert 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 << 10
    configs = {}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and TEXT.match(c["why"])
        assert c["file"].startswith("chipbench/configs/")
        cfg = configs[c["name"]] = bench.load_json(
            os.path.join(root, c["file"]))
        assert cfg["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"] if is_width(k)], c["reduced"]
        assert any(c["name"] == w["config"] for w in spec["workloads"])
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert TEXT.match(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 2)
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    assert len(cell_names) == len(set(cell_names))
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in ("host_clock", "device_trace")
        limit = 0.25
        assert 0.01 <= m["bound"] <= limit
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert TEXT.match(m["layer"])
        assert not mfu_refused(m, spec, configs), m["name"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cell_names)) <= set(cell_names)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files_by_name(cell):
    check_cell(cell)


def test_benchmark_json_keeps_to_its_contract():
    check_contract()


@pytest.mark.parametrize("key,width", [
    ("vocab_size", False), ("num_hidden_layers", False),
    ("n_routed_experts", False), ("hidden_size", True),
    ("intermediate_size", True), ("moe_intermediate_size", True),
    ("kv_lora_rank", True), ("qk_rope_head_dim", True),
    ("num_experts_per_tok", True), ("mamba_expand", True)])
def test_reduced_may_cut_depth_experts_and_vocabulary_but_no_width(key,
                                                                   width):
    assert is_width(key) == width


@pytest.mark.parametrize("name,listed,refused", [
    ("step_mfu", ["model_cell"], False),
    ("step_mfu", ["perftest_cell"], True),
    ("step_mfu", None, True),
    ("device.idle_share.msg_rate", ["perftest_cell"], False)])
def test_mfu_is_refused_only_where_a_cell_names_no_model(name, listed,
                                                         refused):
    spec = {"workloads": [{"name": "model_cell", "config": "model"},
                          {"name": "perftest_cell", "config": "perftest"}]}
    configs = {"model": {MODEL_KEY: "deepseek_v2"}, "perftest": {}}
    metric = {"name": name, **({"workloads": listed} if listed else {})}
    assert mfu_refused(metric, spec, configs) == refused


def test_a_cell_without_its_tiny_file_names_the_missing_path(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    cell = SPEC["workloads"][0]
    missing = tiny_files(cell)[1]
    os.remove(tmp_path / missing)
    with pytest.raises(AssertionError, match=re.escape(missing)):
        check_cell(cell["name"], str(tmp_path))
