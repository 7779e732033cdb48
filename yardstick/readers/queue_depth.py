"""A percentile of the pending-pod count over the window's samples."""

from ..quantiles import percentile


def read(facts, args):
    samples = facts.get("queue_depth") or []
    return percentile(samples, args["q"]) if samples else None
