"""Cell loops: one per traffic ``loop`` kind, each a closed loop over the
program's public entry points.

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
from collections import deque

import numpy as np

from chipbench import loadgen, refs

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


# ---------------------------------------------------------------------------
# verbs: closed loop of doorbell batches on one QP
# ---------------------------------------------------------------------------

class VerbsClosedLoop(Loop):
    """perftest-style closed loop: ``batch`` one-sided WQEs of
    ``message_words`` words per doorbell, ``doorbells_in_flight`` doorbells
    outstanding. A WQE is complete once its CQE is polled and the pool its
    flush produced is ready on the device."""

    def setup(self) -> None:
        from repro.core.rdma import Opcode, RDMAEngine
        cfg, tr = self.config, self.traffic
        self.words = cfg["pool_words_per_peer"]
        self.msg, self.batch = tr["message_words"], tr["batch"]
        self.depth = tr["doorbells_in_flight"]
        self.opcode = Opcode[tr["opcode"]]
        self.engine = RDMAEngine(n_peers=cfg["n_peers"],
                                 pool_size=self.words)
        transport_kind(self.engine, cfg)
        # one QP from peer 1 to peer 0; a READ copies peer 0 -> 1, a WRITE
        # peer 1 -> 0, so the source row is the one filled from the seed
        self.src_peer = 0 if self.opcode is Opcode.READ else 1
        self.source = loadgen.source_words(self.seed, self.words)
        fill_row(self.engine, self.src_peer, 0, self.source)
        self.mr = self.engine.register_mr(0, 0, self.words)
        self.qp = self.engine.create_qp(1, 0)
        remote, local = loadgen.verbs_tape(
            self.seed, self.words, self.msg, self.batch, tr["tape_batches"])
        self.tape = (remote, local)
        self._remote = (remote * self.msg).tolist()
        self._local = (local * self.msg).tolist()
        self.batches = 0                    # doorbells rung so far
        self.bad_cqes = 0
        self._loop(n_batches=tr["warmup_batches"])
        self.engine.transport.pool.block_until_ready()

    def _post(self, b: int) -> int:
        from repro.core.rdma import WQE
        t = b % len(self._remote)
        rem, loc = self._remote[t], self._local[t]
        eng, qp, op, n, rkey = (self.engine, self.qp, self.opcode, self.msg,
                                self.mr.rkey)
        first = b * self.batch
        for j in range(self.batch):
            eng.post_send(qp, WQE(op, qp.qp_num, first + j, local_addr=loc[j],
                                  remote_addr=rem[j], length=n, rkey=rkey))
        return first

    def _complete(self, pool, first: int) -> int:
        from repro.core.rdma import CQEStatus
        with self.spans("bench.wait"):
            pool.block_until_ready()
        with self.spans("bench.poll"):
            cqes = self.engine.poll_cq(self.qp, max_entries=self.batch)
            good = sum(1 for i, c in enumerate(cqes)
                       if c.wr_id == first + i
                       and c.status is CQEStatus.SUCCESS)
        self.bad_cqes += self.batch - good
        return good

    def _loop(self, n_batches: int | None = None,
              stop: float | None = None) -> tuple[int, float]:
        """Run doorbells until ``n_batches`` or the clock reaches ``stop``;
        returns the WQEs completed well and the time the last completed."""
        spans, eng, qp = self.spans, self.engine, self.qp
        inflight: deque = deque()
        done, t_last, start = 0, time.perf_counter(), self.batches
        while True:
            if n_batches is not None and self.batches - start >= n_batches:
                break
            if stop is not None and time.perf_counter() >= stop:
                break
            with spans("bench.post"):
                first = self._post(self.batches)
            with spans("bench.flush"):
                eng.ring_sq_doorbell(qp)
            self.batches += 1
            inflight.append((eng.transport.pool, first))
            if len(inflight) >= self.depth:
                done += self._complete(*inflight.popleft())
                t_last = time.perf_counter()
        while inflight:
            done += self._complete(*inflight.popleft())
            t_last = time.perf_counter()
        return done, t_last

    def window(self) -> None:
        b0, bad0 = self.batches, self.bad_cqes
        t0 = time.perf_counter()
        with self.spans("bench.window"):
            done, t1 = self._loop(stop=t0 + self.seconds)
        self.window_bounds = (t0, t1)
        posted = (self.batches - b0) * self.batch
        self.attempted, self.failed = posted, self.bad_cqes - bad0
        nbytes = done * self.msg * 4
        self.counters.update(wqes_posted=posted, wqes_completed=done,
                             payload_bytes=nbytes)
        self.metrics["msg_rate"] = done / (t1 - t0)
        self.metrics["goodput"] = nbytes / (t1 - t0) / 1e9

    def collect(self) -> None:
        self.rows = [np.asarray(self.engine.read_buffer(p, 0, self.words))
                     for p in range(2)]

    def check(self) -> dict:
        remote, local = self.tape
        idx = np.arange(self.batches) % len(remote)
        src_slots, dst_slots = (remote[idx], local[idx])
        if self.src_peer == 1:
            src_slots, dst_slots = dst_slots, src_slots
        dst_peer = 1 - self.src_peer
        want = refs.copy_replay(self.source, np.zeros(self.words, np.float32),
                                src_slots, dst_slots, self.msg)
        bad = (refs.bad_words(self.rows[dst_peer], want)
               + refs.bad_words(self.rows[self.src_peer], self.source))
        return {"bad_words": (bad, 0), "bad_cqes": (self.bad_cqes, 0)}


# ---------------------------------------------------------------------------
# gradient all-reduce: closed loop over four chips
# ---------------------------------------------------------------------------

class AllReduceClosedLoop(Loop):
    """Back-to-back ``RDMACollective.all_reduce`` of one bucket per rank,
    from host shards to summed host copies."""

    def setup(self) -> None:
        from repro.core.rdma import RDMAEngine
        from repro.train.collectives import RDMACollective
        cfg, tr = self.config, self.traffic
        self.n, self.words = cfg["n_peers"], cfg["bucket_words"]
        self.engine = RDMAEngine(n_peers=self.n,
                                 pool_size=cfg["pool_words_per_peer"])
        transport_kind(self.engine, cfg)
        self.coll = RDMACollective(self.engine, algorithm=cfg["algorithm"])
        self.buckets = loadgen.gradient_buckets(
            self.seed, tr["distinct_buckets"], self.n, self.words)
        for j in range(tr["warmup_allreduces"]):
            self.coll.all_reduce(list(self.buckets[j % len(self.buckets)]))
        # the all-reduces checked: a sample drawn from the seed among the
        # first ``check_range`` of the window, each copied into host buffers
        # made and touched here. The window holds no result of the program,
        # so the check puts no pressure on host memory, and every seed does
        # the same work.
        self.sampled = sorted(loadgen.sample(
            self.seed, tr["check_range"], tr["check_sample"]))
        self.kept = np.ones((len(self.sampled), self.n, self.words),
                            np.float32)

    def window(self) -> None:
        from repro.train.collectives import CollectiveError
        spans, coll, buckets = self.spans, self.coll, self.buckets
        slot_of = {j: i for i, j in enumerate(self.sampled)}
        self.checked: list[int] = []
        j = failed = 0
        t0 = time.perf_counter()
        stop = t0 + self.seconds
        with spans("bench.window"):
            while time.perf_counter() < stop:
                with spans("bench.allreduce"):
                    try:
                        out = coll.all_reduce(list(buckets[j % len(buckets)]))
                    except CollectiveError:
                        failed += 1
                        out = None
                if j in slot_of and out is not None:
                    with spans("bench.keep"):
                        for p, copy in enumerate(out):
                            np.copyto(self.kept[slot_of[j], p], copy)
                    self.checked.append(j)
                out = None
                j += 1
        t1 = time.perf_counter()
        self.window_bounds = (t0, t1)
        done = j - failed
        bucket_bytes = self.words * 4
        self.attempted, self.failed = j, failed
        self.counters.update(
            allreduces_completed=done, bucket_bytes=bucket_bytes,
            ring_wire_bytes_per_chip=2 * (self.n - 1) / self.n * bucket_bytes
            * done)
        # nccl-tests' algbw: the bucket's bytes per all-reduce completed
        self.metrics["algbw"] = done * bucket_bytes / (t1 - t0) / 1e9

    def collect(self) -> None:
        pass                                # the sums are on the host

    def release(self) -> None:
        self.coll = None
        super().release()

    def check(self) -> dict:
        worst = 0.0
        for j in self.checked:
            worst = max(worst, refs.sum_error(
                self.buckets[j % len(self.buckets)],
                self.kept[self.sampled.index(j)]))
        limit = self.config["check_limits"]["sum_error"]
        return {"sum_error": (worst, limit),
                "failed_allreduces": (self.failed, 0),
                "none_checked": (int(not self.checked), 0)}


LOOPS = {
    "verbs_closed_loop": VerbsClosedLoop,
    "allreduce_closed_loop": AllReduceClosedLoop,
}
