"""Pods bound in the window over drains dispatched in it."""

from . import drains


def read(facts, args):
    n = drains(facts)
    return facts["bound"] / n if n else None
