"""Device busy time from the trace (union of the operations' intervals in
the window) per WQE completed, in microseconds."""


def read(run):
    done = run.counters.get("wqes_completed", 0)
    if run.trace is None or not done:
        return None
    return run.trace["busy_s"] / done * 1e6
