"""The descriptor executor's share of its HBM roofline: the least time the
chip needs to read and write the payload bytes once each at its published
HBM bandwidth, over the device busy time, in percent. Payload bytes, not
pool words, so that packing values more densely later reads as a gain."""


def read(run):
    nbytes = run.counters.get("payload_bytes", 0)
    if run.trace is None or run.peaks is None or not nbytes:
        return None
    least = 2 * nbytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / run.trace["busy_s"]
