"""The parts that every cell's loop shares: the ``Loop`` base class, the
benchmark's host spans, and helpers for filling rows and checking the
transport. Each loop kind is a file of its own, ``loops/<kind>.py``
(``bench.loop_module``), a closed loop over the program's public entry
points.

A loop is built from a configuration, a traffic mix and a seed, and goes
through ``setup`` (build, fill, warm every shape the window uses), ``window``
(the measured loop), ``collect`` (read back what the program produced),
``release`` (drop the program's state) and ``check`` (compare with the plain
reference). It leaves its numbers in ``metrics`` (end to end), ``counters``
(for the per-layer readers), ``attempted`` and ``failed``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

#: words per ``write_buffer`` call when a row is filled (64 MiB of f32)
FILL_WORDS = 1 << 24


class Spans:
    """The benchmark's own host spans around the calls it makes. Kept in
    memory; with ``annotate`` also written into the profiler's trace."""

    def __init__(self):
        self.events: list[tuple[str, float, float]] = []
        self.annotate = False

    def __call__(self, name: str):
        return _Span(self, name)

    def total(self, *names: str) -> float:
        return sum(e - s for n, s, e in self.events if n in names)


class _Span:
    __slots__ = ("spans", "name", "t0", "ann")

    def __init__(self, spans: Spans, name: str):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.ann = None
        if self.spans.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.spans.events.append((self.name, self.t0, t1))
        return False


def fill_row(engine, peer: int, base: int, words: np.ndarray) -> None:
    """Write ``words`` at ``base`` of ``peer``'s row in a few large
    ``write_buffer`` calls."""
    for off in range(0, words.size, FILL_WORDS):
        engine.write_buffer(peer, base + off, words[off:off + FILL_WORDS])


def transport_kind(engine, config: dict) -> None:
    got = type(engine.transport).__name__
    if got != config["transport"]:
        raise RuntimeError(f"the engine chose {got}; the configuration "
                           f"runs on {config['transport']}")


class Loop:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, spans: Spans):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.spans = seed, seconds, spans
        self.metrics: dict[str, float] = {}
        self.counters: dict[str, float] = {}
        self.attempted = self.failed = 0
        self.window_bounds = (0.0, 0.0)

    def release(self) -> None:
        self.engine = None
        gc.collect()
