"""The wave step's share of its HBM roofline: the least time the chip
needs to write the output the algorithm must produce -- every valid
edge as two 32-bit ids (8 bytes; every cell has n < 2^32) -- at the
published HBM bandwidth, over the device time of the program under
test's programs in the profiler half of the traced window.

The bytes are counted from the output (the consumer's valid-edge
count), not from any implementation, so they stay the same whatever
computes them.  No integer peak of the vector unit is published, so
this is the only roofline the hashing and sorting have."""

BYTES_PER_EDGE = 8


def read(rd):
    t = rd.trace
    if t is None or "hbm_bytes_per_s" not in rd.peaks:
        return None
    busy = t.program_seconds()
    edges = rd.device.get("edges", 0)
    if busy <= 0 or edges <= 0:
        return None
    return 100.0 * edges * BYTES_PER_EDGE / rd.peaks["hbm_bytes_per_s"] / busy
