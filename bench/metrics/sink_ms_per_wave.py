"""Host milliseconds per wave spent handing it to the consumer: the
runtime's ``wave/sink`` spans plus the benchmark's own consumer call
(``bench/consume``), over the waves of the span half."""


def read(rd):
    s = [r.seconds for r in rd.spans if r.name in ("wave/sink", "bench/consume")]
    waves = rd.host.get("waves", 0)
    return 1e3 * sum(s) / waves if s and waves else None
