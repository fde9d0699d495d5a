"""Reduction of a profiler trace to device busy time, idle gaps and op times.

Busy time is the union of the intervals in which a program ran on a
device, inside the benchmark's ``bench.window`` span, averaged over the
devices. Each idle gap is attributed to the innermost benchmark span
(``bench.*``) open on the host while the device idled.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: the device line with one event per program run, and the one per op run
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def segments(spans) -> list[tuple[float, float, str]]:
    """(start, end, name of the innermost open span) pieces covering the
    spans, which come from one host thread and so nest or are disjoint."""
    marks = []
    for i, (_, s, e) in enumerate(spans):
        marks.append((s, 1, -(e - s), i))        # outer opens first
        marks.append((e, 0, 0, i))               # closes before opens
    marks.sort()
    out, stack, t = [], [], None
    for when, opens, _, i in marks:
        if stack and when > t:
            out.append((t, when, spans[stack[-1]][0]))
        t = when
        if opens:
            stack.append(i)
        else:
            stack.remove(i)
    return out


def attribute(gap_list, spans) -> dict[str, float]:
    """Idle seconds by the innermost span open during them; time in no
    span goes to ``"no bench span"``."""
    segs = segments(spans)
    ends = [e for _, e, _ in segs]
    out: dict[str, float] = defaultdict(float)
    for s, e in gap_list:
        left = e - s
        j = bisect.bisect_right(ends, s)
        while j < len(segs) and segs[j][0] < e:
            part = min(e, segs[j][1]) - max(s, segs[j][0])
            if part > 0:
                out[segs[j][2]] += part
                left -= part
            j += 1
        if left > 1e-12:
            out["no bench span"] += left
    return dict(out)


def top(totals: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce_events(host_spans, device_busy, op_time=None,
                  n_top: int = 10) -> dict | None:
    """Reduce plain event lists (seconds on one clock):

    * ``host_spans``: [(name, start, end)] of the benchmark's spans, with
      exactly one ``bench.window``;
    * ``device_busy``: {device: [(start, end)]} of the programs it ran;
    * ``op_time``: {op name: seconds inside the window, summed over the
      devices}.

    Returns busy and window seconds (busy averaged over devices), the ops
    with the most device time and the idle time by host span, each per
    device, or None when the trace holds no window or no device program
    in it."""
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW_SPAN]
    if len(windows) != 1 or not device_busy:
        return None
    lo, hi = windows[0]
    spans = [sp for sp in host_spans if sp[0] != WINDOW_SPAN]
    busy_s, idle = [], defaultdict(float)
    for ivs in device_busy.values():
        busy_s.append(busy(ivs, lo, hi))
        for name, sec in attribute(gaps(ivs, lo, hi), spans).items():
            idle[name] += sec
    n = len(device_busy)
    mean_busy = sum(busy_s) / n
    if mean_busy <= 0:
        return None
    return {
        "busy_s": mean_busy,
        "window_s": hi - lo,
        "devices": n,
        "device_ops": top({k: v / n for k, v in (op_time or {}).items()},
                          n_top),
        "idle_gaps": top({k: v / n for k, v in idle.items()}, n_top),
    }


def trace_file(log_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def op_name(hlo: str) -> str:
    """``%copy.11 = f32[2,64]{1,0} copy(...)`` -> ``copy.11``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def read_trace(path: str):
    """(host spans, device busy intervals, op time) from a profiler
    ``.xplane.pb`` file, in seconds on the trace's clock: the benchmark's
    spans from the host planes, each device's programs from its
    ``XLA Modules`` line, and the time of each op on its ``XLA Ops`` line
    inside the window, summed over devices (ops nested in a loop count
    with the loop)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host_spans = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        host_spans.append((ev.name, s,
                                           s + ev.duration_ns * 1e-9))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    lo, hi = windows[0] if len(windows) == 1 else (0.0, 0.0)
    device_busy, op_time = {}, defaultdict(float)
    for plane in data.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if MODULE_LINE not in lines:
            continue
        device_busy[plane.name] = [
            (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in lines[MODULE_LINE].events]
        if OP_LINE in lines:
            for ev in lines[OP_LINE].events:
                s = ev.start_ns * 1e-9
                part = min(s + ev.duration_ns * 1e-9, hi) - max(s, lo)
                if part > 0:
                    op_time[op_name(ev.name)] += part
    return host_spans, device_busy, dict(op_time)
