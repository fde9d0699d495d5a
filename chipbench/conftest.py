import copy
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

#: the cells at a size the CPU runs in seconds; widths of the published
#: configurations are cut here only, never in the benchmark's own files
TINY = {
    "rdma_perftest_1chip": {"pool_words_per_peer": 1 << 14},
    "ddp_grad_sync_4chip": {"pool_words_per_peer": 1 << 12,
                            "bucket_words": 1000,
                            "transport": "LocalTransport"},
}
TINY_TRAFFIC = {
    "read_64B_b50": {"tape_batches": 8, "warmup_batches": 2},
    "read_1MiB_b50": {"message_words": 128, "batch": 8, "tape_batches": 8,
                      "warmup_batches": 1},
    "allreduce_25MiB_ring": {},
}


def make_tiny_root(path: str, ici: bool = False) -> str:
    """A copy of the benchmark at a tiny size under ``path``: the same
    BENCHMARK.json, loops and readers, with small pools and buckets."""
    os.makedirs(os.path.join(path, "chipbench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(path, "chipbench", "traffic"), exist_ok=True)
    shutil.copytree(os.path.join(HERE, "metrics"),
                    os.path.join(path, "chipbench", "metrics"),
                    dirs_exist_ok=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        cfg.update(copy.deepcopy(TINY[c["name"]]))
        if ici and cfg["n_peers"] == 4:
            cfg["transport"] = "ICITransport"
        json.dump(cfg, open(os.path.join(path, c["file"]), "w"))
    for w in bench["workloads"]:
        name = w["traffic"]
        tr = json.load(open(os.path.join(HERE, "traffic", name + ".json")))
        tr.update(copy.deepcopy(TINY_TRAFFIC[name]))
        json.dump(tr, open(os.path.join(path, "chipbench", "traffic",
                                        name + ".json"), "w"))
    return path


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))
