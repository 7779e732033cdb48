"""The sender's clock around ``create_many``. ``stat`` is ``percentile``
(of a call's duration, with ``q``) or ``ms_per_kpod`` (first send to last
return, per thousand pods created)."""

from ..quantiles import percentile


def read(facts, args):
    calls = [g for g in facts["groups"] if g[2] is not None and g[4] is None]
    if not calls:
        return None
    if args["stat"] == "percentile":
        return percentile([(g[3] - g[2]) * 1000.0 for g in calls], args["q"])
    if args["stat"] == "ms_per_kpod":
        span = max(g[3] for g in calls) - min(g[2] for g in calls)
        return span * 1000.0 / (sum(g[1] for g in calls) / 1000.0)
    raise ValueError(f"create_call stat {args['stat']!r}")
