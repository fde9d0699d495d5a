"""The readers of the program's own spans, on traced tiny runs on the CPU:
each reports in its cell, the collective's host copies to the byte, and
nothing without the profiler running or without the recorder; a window
whose records were partly dropped fails."""
import sys
import time
from types import SimpleNamespace

import pytest

from chipbench import bench

VERBS = ("engine.flush_self_us_per_wqe.msg_rate",
         "transport.dispatch_us_per_wqe.msg_rate")
CLIENT = ("client.host_reduce_ms.algbw",
          "client.host_copy_bytes_per_byte.algbw")


def run(root, workload, trace):
    return bench.run_cell(workload, 2 ** 31 + 11, 0.3, trace,
                          time.perf_counter(), require_chip=False, root=root,
                          log=lambda *a: None)


def _pow2_row(words, pool):
    """Words of the padded staging row a host write of ``words`` uploads."""
    return min(max(16, 1 << (words - 1).bit_length()),
               1 << (pool - 1).bit_length())


def host_copy_per_byte(n, words, pool):
    """Host<->device bytes of one ring all-reduce per byte of one rank's
    bucket: every rank's padded shard up, per reduce-scatter round each
    rank reads two chunks and stages one padded chunk, every rank's sum
    back."""
    cw = -(-words // n)
    load = n * _pow2_row(cw * n, pool)
    reads = (n - 1) * n * 2 * cw
    writes = (n - 1) * n * _pow2_row(cw, pool)
    readout = n * words
    return (load + reads + writes + readout) / words


def test_the_hand_count_at_the_cells_size():
    assert host_copy_per_byte(4, 6553600, 1 << 24) == pytest.approx(18.96)


def test_traced_verbs_run_reports_flush_and_dispatch_time(tiny_root):
    res = run(tiny_root, "verbs_read_64B_b50", True)
    assert res["correct"]
    for name in VERBS:
        assert res["metrics"][name]["value"] > 0
    assert not set(CLIENT) & set(res["metrics"])


def test_traced_allreduce_reports_reduce_time_and_host_copies(tiny_root):
    res = run(tiny_root, "allreduce_25MiB_ring", True)
    assert res["correct"]
    cfg = bench.resolve("allreduce_25MiB_ring", tiny_root).config
    assert res["metrics"]["client.host_reduce_ms.algbw"]["value"] > 0
    assert res["metrics"]["client.host_copy_bytes_per_byte.algbw"][
        "value"] == pytest.approx(host_copy_per_byte(
            4, cfg["bucket_words"], cfg["pool_words_per_peer"]))
    assert not set(VERBS) & set(res["metrics"])


@pytest.mark.parametrize("workload",
                         ["verbs_read_64B_b50", "allreduce_25MiB_ring"])
def test_untraced_run_reports_no_program_span_metric(tiny_root, workload):
    res = run(tiny_root, workload, False)
    assert not (set(VERBS) | set(CLIENT)) & set(res["metrics"])


@pytest.mark.parametrize("name", VERBS + CLIENT)
def test_a_program_without_the_recorder_gives_nothing(monkeypatch, name):
    import repro.core.rdma
    monkeypatch.delattr(repro.core.rdma, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.rdma.trace", None)
    run = SimpleNamespace(spans=SimpleNamespace(
        events=[("bench.window", 0.0, 1e12)]))
    read = bench.reader({"name": name, "moves": name.rsplit(".", 1)[1]})
    assert read(run) is None


@pytest.mark.parametrize("name", VERBS + CLIENT)
def test_a_window_with_dropped_records_fails(monkeypatch, name):
    from repro.core.rdma import trace
    monkeypatch.setattr(trace, "dropped", 1)
    run = SimpleNamespace(spans=SimpleNamespace(
        events=[("bench.window", 0.0, 1e12)]))
    read = bench.reader({"name": name, "moves": name.rsplit(".", 1)[1]})
    with pytest.raises(RuntimeError, match="dropped 1"):
        read(run)
