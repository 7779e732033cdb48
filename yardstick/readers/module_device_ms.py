"""Device time of one XLA module in the profiler trace, per execution:
the events of the ``XLA Modules`` line whose name holds ``module``."""


def read(facts, args):
    trace = facts.get("trace")
    if not trace:
        return None
    hits = [m for name, m in trace["modules"].items()
            if args["module"] in name]
    n = sum(m["n"] for m in hits)
    # m["s"] is averaged over the chips and m["n"] counts every chip's events
    return (sum(m["s"] for m in hits) * 1000.0 * trace["chips"] / n
            if n else None)
