"""Device nanoseconds per edge and descent level of the R-MAT wave step:
the device time of the program under test's programs in the profiler
half of the traced window, over the valid edges of that half times the
levels of the descent (``levels`` of the ``plan/rmat`` span, the span
half's).  ``None`` where either is missing."""


def read(rd):
    levels = [r.attrs["levels"] for r in rd.spans
              if r.name == "plan/rmat" and "levels" in r.attrs]
    t = rd.trace
    edges = rd.device.get("edges", 0)
    if not levels or t is None or edges <= 0:
        return None
    busy = t.program_seconds()
    return 1e9 * busy / (edges * levels[0]) if busy > 0 else None
