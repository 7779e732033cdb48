"""Host-clock busy time of the named spans, per dispatched drain."""

from . import drains


def read(facts, args):
    n = drains(facts)
    found = [facts["spans"][s]["ms"] for s in args["spans"]
             if s in facts["spans"]]
    if not n or not found:
        return None
    return sum(found) / n
