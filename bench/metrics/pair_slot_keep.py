"""Share of the pair step's candidate edge slots that hold an edge:
valid edges over the slots the wave step computed (every row of every
chunk's validity mask, padding rows of a ragged last wave included),
over the traced window.  Counted from the output and its shapes."""


def read(rd):
    slots = rd.device.get("slots", 0) + rd.host.get("slots", 0)
    edges = rd.device.get("edges", 0) + rd.host.get("edges", 0)
    return 100.0 * edges / slots if slots else None
