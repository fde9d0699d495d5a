"""verbs: closed loop of doorbell batches on one QP (perftest-style), for
the traffic mixes whose ``loop`` is ``verbs_closed_loop``."""
from __future__ import annotations

import functools
import time
from collections import deque

import numpy as np

from chipbench import loadgen, refs
from chipbench.cells import Loop, fill_row, transport_kind
from chipbench.faults import both_transports


class VerbsClosedLoop(Loop):
    """perftest-style closed loop: ``batch`` one-sided WQEs of
    ``message_words`` words per doorbell, ``doorbells_in_flight`` doorbells
    outstanding. A WQE is complete once its CQE is polled and the pool its
    flush produced is ready on the device.

    Each doorbell is waited on through a one-word read of the pool its flush
    produced, dispatched before the next doorbell rings, as a client polls a
    completion word: the read stays valid where the executor donates the
    pool it is given, so that the next doorbell deletes that pool."""

    def setup(self) -> None:
        import jax
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
        self._mark = jax.jit(lambda pool: pool[0, :1])
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

    def _complete(self, mark, first: int) -> int:
        from repro.core.rdma import CQEStatus
        with self.spans("bench.wait"):
            mark.block_until_ready()
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
            with spans("bench.mark"):
                inflight.append((self._mark(eng.transport.pool), first))
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


def truncated():
    """Control of the verbs cells, which state no precision: it
    breaks the guarantee that a transfer delivers all ``length`` words, by
    leaving the last word of every transfer undelivered."""
    def wrap(orig):
        @functools.wraps(orig)
        def run(self, plan):
            return orig(self, [e[:5] + (max(0, e[5] - 1),) for e in plan])
        return run
    return both_transports("execute_batch", wrap)


LOOP = VerbsClosedLoop
FAULTS = ("unchanged", "half_batch", "altered")
CONTROL = "truncated"
