"""Bytes the collective copies between host and device (padded staging
rows up, reads back) per byte of bucket all-reduced on one rank: the
``bytes`` of the program's ``rdma.qdma.h2d`` and ``rdma.qdma.d2h`` spans
under an ``rdma.coll.allreduce``, over its ``buckets`` x ``bucket_bytes``.
Host shards in and summed host copies out alone need 2 x ranks: each
rank's shard loaded once and its sum read back once."""
from chipbench import program_spans as ps


def read(run):
    recs = ps.window_records(run)
    if recs is None:
        return None
    calls = ps.named(recs, "rdma.coll.allreduce")
    moved = sum(r.attrs.get("buckets", 0) * r.attrs.get("bucket_bytes", 0)
                for r in calls)
    if not moved:
        return None
    copies = [r for r in ps.under(recs, calls)
              if r.name in ("rdma.qdma.h2d", "rdma.qdma.d2h")]
    return sum(r.attrs["bytes"] for r in copies) / moved
