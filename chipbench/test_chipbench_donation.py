"""The verbs loop at a tiny size on the CPU with an executor that donates the
pool it is given: each doorbell is waited on through a handle that the next
doorbell does not delete, and the run stays correct."""
import time

import pytest

from chipbench import bench

VERBS = [w["name"] for w in bench.spec()["workloads"]
         if bench.resolve(w["name"]).traffic["loop"] == "verbs_closed_loop"]


@pytest.mark.parametrize("workload", VERBS)
def test_verbs_cell_is_correct_with_a_donated_pool(tiny_root, workload,
                                                   monkeypatch):
    import jax
    from repro.core.rdma import transport as tp
    donating = jax.jit(tp._exec_descriptors_local.__wrapped__,
                       static_argnames=("chunk",), donate_argnums=0)
    donated = []

    def run(pool, desc, chunk):
        out = donating(pool, desc, chunk)
        donated.append(pool.is_deleted())
        return out

    run._cache_size = donating._cache_size    # read around the window
    monkeypatch.setattr(tp, "_exec_descriptors_local", run)
    res = bench.run_cell(workload, 2 ** 31 + 13, 0.3, False,
                         time.perf_counter(), require_chip=False,
                         root=tiny_root, log=lambda *a: None)
    assert donated and all(donated)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
