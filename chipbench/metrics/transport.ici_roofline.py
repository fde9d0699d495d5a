"""The ICI executor's share of its interconnect roofline: the ring's ideal
bytes on the wire per chip, 2(n-1)/n of the bucket per all-reduce, at the
published chip-to-chip bandwidth, over the device busy time, in percent."""


def read(run):
    wire = run.counters.get("ring_wire_bytes_per_chip", 0)
    if run.trace is None or run.peaks is None or not wire:
        return None
    return 100.0 * wire / run.peaks["ici_bytes_per_s"] / run.trace["busy_s"]
