"""The harness: finds a cell's configuration, traffic, loop and per-layer
readers by name in ``BENCHMARK.json``, runs the cell's loop once, and
assembles the result line.

A cell is added with new files and new entries alone; no file the benchmark
already has changes:

- ``configs/<config>.json``: the configuration as it is run, and, where
  the references of ``refs.py`` do not serve it, a module of its own plain
  reference under ``chipbench/``;
- ``traffic/<traffic>.json``: the traffic mix; its ``loop`` key names the
  loop kind that reads it;
- ``loops/<loop>.py``, for a new loop kind: ``LOOP`` (a ``cells.Loop``),
  ``FAULTS`` (the faults its cells can have) and ``CONTROL`` (its control),
  with the factory of any fault or control not in ``faults.SHARED``
  (``loop_module``, ``faults.plant``);
- ``tiny/configs/<config>.json`` and ``tiny/traffic/<traffic>.json``: the
  keys the CPU tests change to run the cell at a tiny size;
- ``metrics/<quantity>.py``, for a new per-layer metric: its reader,
  ``read(run) -> float | None`` (``reader`` says how it is named);
- in ``BENCHMARK.json``: the configuration and the cell, new metrics, and
  the cell's name appended to the ``workloads`` list of each metric it
  reports.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
import tempfile
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
#: JAX's persistent compile cache of the checkout (fixed, so it hits)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """The chips the cell asks for are not there."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> SimpleNamespace:
    """The cell, its configuration, its traffic and the metrics it reports,
    found by name."""
    b = spec(root)
    cells = {w["name"]: w for w in b["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in b["configs"]}[cell["config"]]
    return SimpleNamespace(
        cell=cell,
        config=load_json(os.path.join(root, config_entry["file"])),
        traffic=load_json(os.path.join(root, "chipbench", "traffic",
                                       cell["traffic"] + ".json")),
        end_to_end=[m for m in b["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in b["per_layer"] if applies(m, workload)],
        run_seconds=b["run_seconds"])


@functools.cache
def _load(path: str, prefix: str):
    """The module in the file ``path``, loaded once in a process: a fault
    that patches a loop's class patches the class that runs."""
    stem = os.path.basename(path)[:-len(".py")]
    name = prefix + stem.replace(".", "_").replace("-", "_")
    sp = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def reader(metric: dict, root: str = ROOT):
    """The ``read`` function of ``metrics/<quantity>.py``, where the quantity
    is the metric's name less a trailing ``.<moves>``: one reader serves a
    quantity split by the end-to-end metric it moves."""
    name, suffix = metric["name"], "." + metric["moves"]
    if name.endswith(suffix):
        name = name[:-len(suffix)]
    return _load(os.path.join(root, "chipbench", "metrics", name + ".py"),
                 "chipbench_metric_").read


def loop_module(kind: str, root: str = ROOT):
    """``loops/<kind>.py``, the loop of the traffic mixes whose ``loop`` is
    ``kind``: its ``LOOP`` class, ``FAULTS`` and ``CONTROL``."""
    return _load(os.path.join(root, "chipbench", "loops", kind + ".py"),
                 "chipbench_loop_")


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def enable_compile_cache() -> str:
    """Persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``.jax_cache/`` of the checkout. Every program is cached, however
    quick to compile, so a second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Programs lowered in this process (a new program, compiled or taken
    from the persistent cache), counted from JAX's monitoring events."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **kw) -> None:
        if name == self.EVENT:
            self.count += 1


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def span_summary(spans, listed: int = 16) -> dict:
    """Per span name: count and total seconds, and each span's seconds where
    there are at most ``listed`` of them (one per all-reduce, say)."""
    by: dict[str, list[float]] = {}
    for name, t0, t1 in spans.events:
        by.setdefault(name, []).append(t1 - t0)
    return {name: {"n": len(d), "total_s": sum(d),
                   **({"each_s": d} if len(d) <= listed else {})}
            for name, d in by.items()}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             start: float, require_chip: bool = True, root: str = ROOT,
             log=print) -> dict:
    """Run one cell once and return its result object; ``start`` is the
    ``time.perf_counter()`` of the process's start, from which set-up is
    timed. Raises ``NoChip`` without the cell's TPU chips (unless
    ``require_chip`` is false, as in the CPU tests)."""
    import jax
    from chipbench.cells import Spans
    from repro.core.rdma import transport as tp

    r = resolve(workload, root)
    devices = jax.devices()
    chips = r.cell["chips"]
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"{workload} needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    if require_chip:
        enable_compile_cache()
    dev = device_info(devices)
    log(f"device: {dev['platform']} {dev['kind']} x{dev['count']}; "
        f"workload {workload}, seed {seed}, {seconds} s, trace {int(trace)}")
    counter = CompileCounter()
    spans = Spans()
    loop = loop_module(r.traffic["loop"], root).LOOP(
        r.config, r.traffic, seed, seconds, spans)
    loop.setup()
    setup_s = time.perf_counter() - start
    spans.events.clear()
    lowered0 = counter.count
    cache0 = (tp.descriptor_cache_size(), tp.staging_cache_size())
    summary = None
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        if trace:
            import jax.profiler
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            spans.annotate = True
        try:
            loop.window()
        finally:
            if trace:
                spans.annotate = False
                jax.profiler.stop_trace()
        compiles = (counter.count - lowered0,
                    tp.descriptor_cache_size() - cache0[0],
                    tp.staging_cache_size() - cache0[1])
        if trace:
            from chipbench import tracing
            path = tracing.trace_file(tdir)
            summary = tracing.reduce_events(*tracing.read_trace(path)) \
                if path else None
            if summary is None and require_chip:
                raise RuntimeError("the trace holds no device operation "
                                   "inside the window")
    log(f"compiles in window: {compiles[0]} programs lowered, "
        f"{compiles[1]} descriptor, {compiles[2]} staging")
    log(f"setup_s {setup_s:.6f}; counters " + json.dumps(loop.counters))
    log("window spans " + json.dumps(span_summary(spans)))
    dev["memory_peak_bytes"] = memory_peak(devices)
    loop.collect()
    loop.release()
    checks = loop.check()
    run = SimpleNamespace(
        spans=spans, counters=loop.counters,
        trace=summary, peaks=peaks(dev["kind"]) if require_chip else None,
        window_s=loop.window_bounds[1] - loop.window_bounds[0],
        cell=r.cell, config=r.config, traffic=r.traffic)
    metrics = {}
    if trace:
        for m in r.per_layer:
            value = reader(m, root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            dev["busy_s"] = summary["busy_s"]
            dev["window_s"] = summary["window_s"]
    else:
        values = dict(loop.metrics, setup_s=setup_s)
        for m in r.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    correct = all(v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": loop.attempted,
              "failed": loop.failed, "metrics": metrics, "device": dev}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def print_result(result: dict) -> None:
    for k, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {k}: {c['value']} (limit {c['limit']}) {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
