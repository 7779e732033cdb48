"""One definition of a percentile for every metric of the yardstick."""

import math


def percentile(values, q: float) -> float:
    """Nearest rank: the smallest value with at least ``q`` of the samples
    at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]
