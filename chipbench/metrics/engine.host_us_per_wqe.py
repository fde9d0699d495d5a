"""Host time of the engine per WQE: the benchmark's spans around posting,
ringing (which flushes: schedule, admit, coalesce, pack, upload, dispatch)
and polling, over the WQEs posted in the window, in microseconds."""


def read(run):
    posted = run.counters.get("wqes_posted", 0)
    if not posted:
        return None
    host = run.spans.total("bench.post", "bench.flush", "bench.poll")
    return host / posted * 1e6
