"""Host time of the engine's flush less the transport's dispatch inside
it (schedule, admit, coalesce, service ledger, CQE delivery), per WQE
scheduled, in microseconds: the program's ``rdma.flush`` spans less their
``rdma.transport.execute`` children, over the flushes' ``wqes``."""
from chipbench import program_spans as ps


def read(run):
    recs = ps.window_records(run)
    if recs is None:
        return None
    flushes = ps.named(recs, "rdma.flush")
    wqes = sum(r.attrs.get("wqes", 0) for r in flushes)
    if not wqes:
        return None
    ids = {r.span_id for r in flushes}
    dispatch = [r for r in ps.named(recs, "rdma.transport.execute")
                if r.parent_id in ids]
    return (ps.seconds(flushes) - ps.seconds(dispatch)) / wqes * 1e6
