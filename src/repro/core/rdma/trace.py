"""In-program spans of the engine, the transport and the collective.

A span is recorded only while the JAX profiler collects
(``jax.profiler.start_trace`` .. ``stop_trace``); otherwise ``span()``
costs one ``TraceAnnotation.is_enabled()`` call and returns a shared
no-op. While on, each span

* enters a ``jax.profiler.TraceAnnotation`` of its name, so it lands on
  the profiler's host plane, on the clock of the device's events and
  nested inside any annotation the caller opened around it;
* appends ``(name, t0, t1, span_id, parent_id, attrs)`` to a bounded
  in-memory list, times from ``time.perf_counter``. ``parent_id`` is the
  innermost span open on the same thread (``None`` at the top).

Nothing is written to disk: a reader takes ``records(lo, hi)`` when its
run ends. Spans sit at stage boundaries (a flush, a dispatch, a staging
copy, a collective round), never per WQE.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: most records kept; spans closing after that are counted, not kept
MAX_RECORDS = 1 << 18


class Record(NamedTuple):
    name: str
    t0: float
    t1: float
    span_id: int
    parent_id: Optional[int]
    attrs: dict


_records: List[Record] = []
_ids = itertools.count(1)
_local = threading.local()
#: spans that closed while the list was full
dropped = 0


class _NoSpan:
    """What ``span()`` returns while the profiler does not collect."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("name", "attrs", "span_id", "parent_id", "t0", "ann")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def set(self, **attrs) -> None:
        """Attach counts known only at the end of the span."""
        self.attrs.update(attrs)

    def __enter__(self):
        stack = _stack()
        self.parent_id = stack[-1].span_id if stack else None
        self.span_id = next(_ids)
        stack.append(self)
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global dropped
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        _stack().pop()
        if len(_records) < MAX_RECORDS:
            _records.append(Record(self.name, self.t0, t1, self.span_id,
                                   self.parent_id, self.attrs))
        else:
            dropped += 1
        return False


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def span(name: str, **attrs):
    """A context manager timing its body as ``name`` while the profiler
    collects; ``attrs`` (and ``.set(...)`` inside) annotate the record."""
    if not TraceAnnotation.is_enabled():
        return NO_SPAN
    return _Span(name, attrs)


def recording() -> bool:
    """Whether spans are being recorded (the profiler collects)."""
    return TraceAnnotation.is_enabled()


def records(lo: float = float("-inf"), hi: float = float("inf")
            ) -> List[Record]:
    """The spans that closed inside ``[lo, hi]`` (``perf_counter`` time)."""
    return [r for r in _records if lo <= r.t1 <= hi]


def clear() -> None:
    global dropped
    _records.clear()
    dropped = 0
