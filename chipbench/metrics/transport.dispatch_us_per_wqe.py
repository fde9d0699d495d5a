"""Host time of the transport's dispatch (pack the descriptor table,
upload it, launch the executor) per WQE it carried, in microseconds: the
program's ``rdma.transport.execute`` spans over their ``wqes``."""
from chipbench import program_spans as ps


def read(run):
    recs = ps.window_records(run)
    if recs is None:
        return None
    ex = ps.named(recs, "rdma.transport.execute")
    wqes = sum(r.attrs.get("wqes", 0) for r in ex)
    if not wqes:
        return None
    return ps.seconds(ex) / wqes * 1e6
