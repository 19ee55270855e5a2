"""Host milliseconds per wave dispatch: mean of the ``wave/dispatch``
spans of ``repro.obs`` in the span half of the traced window."""


def read(rd):
    d = [r.seconds for r in rd.spans if r.name == "wave/dispatch"]
    return 1e3 * sum(d) / len(d) if d else None
