"""Host milliseconds per wave spent cutting it into the stream's chunks:
the runtime's ``wave/rows`` spans (each mesh row sliced out of a wave)
plus the front door's ``stream/chunk`` spans (each ``EdgeChunk`` built:
a batch-1 stream's ``[0]`` slices, the chunk's edge count), over the
waves of the span half.

Read only where the host runs ahead of the device by less than a wave.
Where the device is the bottleneck, the first eager op after a dispatch,
the row slice, waits there for the wave step, and these spans time the
device instead of host work."""


def read(rd):
    s = [r.seconds for r in rd.spans if r.name in ("wave/rows", "stream/chunk")]
    waves = rd.host.get("waves", 0)
    return 1e3 * sum(s) / waves if s and waves else None
