"""gradient all-reduce: closed loop over four chips, for the traffic mixes
whose ``loop`` is ``allreduce_closed_loop``."""
from __future__ import annotations

import time

import numpy as np

from chipbench import loadgen, refs
from chipbench.cells import Loop, transport_kind
from chipbench.faults import patched


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


def bf16_sum():
    """Control of the all-reduce cell: the plain reference, computed one
    precision below the configuration's float32 (bfloat16), in the place of
    ``RDMACollective.all_reduce``."""
    from repro.train.collectives import RDMACollective

    def all_reduce(self, shards, algorithm=None):
        out = refs.sum_bf16(np.stack([np.asarray(s) for s in shards]))
        return [out.copy() for _ in range(self.n)]
    return patched(RDMACollective, "all_reduce", all_reduce)


LOOP = AllReduceClosedLoop
FAULTS = ("unchanged", "half_batch", "altered", "no_exchange")
CONTROL = "bf16_sum"
