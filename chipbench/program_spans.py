"""The program's own spans (``repro.core.rdma.trace``) inside a run's
``bench.window``, for the per-layer readers that read them.

The program records spans only while the profiler collects, so they exist
only in a run with ``--trace 1``. A program without the recorder, or a run
without spans, gives None and nothing is reported. A recorder that had to
drop records fails the run: a reading of part of the window would pass
for one of all of it.
"""
from __future__ import annotations

from chipbench.tracing import WINDOW_SPAN


def window_records(run):
    """The records that closed inside the run's window, or None."""
    try:
        from repro.core.rdma import trace
    except ImportError:
        return None
    windows = [(s, e) for name, s, e in run.spans.events
               if name == WINDOW_SPAN]
    if len(windows) != 1:
        return None
    if getattr(trace, "dropped", 0):
        raise RuntimeError(f"the span recorder dropped {trace.dropped} "
                           "records: the window's spans are incomplete")
    return trace.records(*windows[0]) or None


def seconds(records) -> float:
    return sum(r.t1 - r.t0 for r in records)


def named(records, name: str) -> list:
    return [r for r in records if r.name == name]


def under(records, ancestors) -> list:
    """The records with one of ``ancestors`` (records) above them."""
    parent = {r.span_id: r.parent_id for r in records}
    top = {r.span_id for r in ancestors}
    out = []
    for r in records:
        p = r.parent_id
        while p is not None and p not in top:
            p = parent.get(p)
        if p is not None:
            out.append(r)
    return out
