"""Host seconds of plan emission per pass: the ``plan/*`` spans of
``repro.obs`` that no other ``plan/*`` span encloses, summed, over the
passes begun in the span half of the traced window."""


def read(rd):
    by_id = {r.span_id: r for r in rd.spans}

    def top(r):
        p = by_id.get(r.parent_id)
        while p is not None:
            if p.name.startswith("plan/"):
                return False
            p = by_id.get(p.parent_id)
        return True

    plan = [r.seconds for r in rd.spans
            if r.name.startswith("plan/") and not r.instant and top(r)]
    passes = rd.host.get("passes", 0)
    if not plan or not passes:
        return None
    return sum(plan) / passes
