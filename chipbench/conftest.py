import json
import os
import shutil
import sys

# Tests run on the CPU with one device and leave the compile cache alone,
# so that a test never takes a chip from the program that owns it.
os.environ.pop("XLA_FLAGS", None)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import pytest  # noqa: E402

from chipbench.bench import load_json as load  # noqa: E402

#: where each configuration's and traffic mix's tiny size is kept: the keys
#: that the CPU tests change, never the benchmark's own files
TINY = os.path.join("chipbench", "tiny")


def tiny_files(cell: dict) -> tuple[str, str]:
    """The tiny files of a cell of ``BENCHMARK.json``, relative to the root:
    its configuration's and its traffic's."""
    return (os.path.join(TINY, "configs", cell["config"] + ".json"),
            os.path.join(TINY, "traffic", cell["traffic"] + ".json"))


def make_tiny_root(path: str, ici: bool = False, src: str = ROOT) -> str:
    """A copy of the benchmark at ``src`` at a tiny size under ``path``: the
    same BENCHMARK.json, loops and readers, each configuration and traffic
    mix updated from its tiny file."""
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(path, "chipbench", d), exist_ok=True)
    for d in ("metrics", "loops"):
        shutil.copytree(os.path.join(src, "chipbench", d),
                        os.path.join(path, "chipbench", d),
                        dirs_exist_ok=True)
    shutil.copy(os.path.join(src, "BENCHMARK.json"), path)
    bench = load(os.path.join(src, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        tiny_config, tiny_traffic = tiny_files(w)
        cfg = load(os.path.join(src, files[w["config"]]))
        cfg.update(load(os.path.join(src, tiny_config)))
        if ici and cfg["n_peers"] == 4:
            cfg["transport"] = "ICITransport"
        dump(cfg, os.path.join(path, files[w["config"]]))
        traffic = os.path.join("chipbench", "traffic", w["traffic"] + ".json")
        tr = load(os.path.join(src, traffic))
        tr.update(load(os.path.join(src, tiny_traffic)))
        dump(tr, os.path.join(path, traffic))
    return path


def dump(obj: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))
