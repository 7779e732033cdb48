"""A ratio of sums of series over the window: ``scale`` x (sum of the
``num`` series' increases) / (sum of the ``den`` series' increases). A
mean from a histogram's ``_sum`` and ``_count``, a thread's share of the
process's CPU time, a counter per drain. None when a ``num`` series is not
in the exposition (the program does not keep it) or the ``den`` sum is 0."""


def read(facts, args):
    window = facts["counters"]
    if any(s not in window for s in args["num"]):
        return None
    den = sum(window.get(s, 0.0) for s in args["den"])
    if not den:
        return None
    return args.get("scale", 1.0) * sum(window[s] for s in args["num"]) / den
