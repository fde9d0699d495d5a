"""Host time of the collective's partial reduces between rounds (read both
operands back, add, stage the sum) per bucket all-reduced, in
milliseconds: the program's ``rdma.coll.reduce`` spans under the
``rdma.coll.allreduce`` spans of the window, over their ``buckets``."""
from chipbench import program_spans as ps


def read(run):
    recs = ps.window_records(run)
    if recs is None:
        return None
    calls = ps.named(recs, "rdma.coll.allreduce")
    buckets = sum(r.attrs.get("buckets", 0) for r in calls)
    if not buckets:
        return None
    reduces = ps.named(ps.under(recs, calls), "rdma.coll.reduce")
    return ps.seconds(reduces) / buckets * 1e3
