"""Open loop: groups of ``group`` pods (one bulk create a group, a
controller scaling a ReplicaSet) at ``rate_pods_per_s`` for ``--seconds``,
each pod timed from when its group was due. The gaps between groups are
the same set for every seed — the quantiles of the exponential distribution
with that rate — in an order the seed shuffles, so every run offers the
same number of pods and the same gaps and only their order differs."""

import math
import random

from ..quantiles import percentile

E2E = {"bind_p50_s": "s", "bind_p99_s": "s"}


def plan(params: dict, config: dict, seed: int, seconds: float) -> dict:
    group = int(params["group"])
    n = int(float(params["rate_pods_per_s"]) * seconds / group)
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    mean = seconds / n
    scale = (seconds - mean / 2) / sum(gaps)
    random.Random(seed).shuffle(gaps)
    groups, t = [], 0.0
    for g in gaps:
        t += g * scale
        groups.append((t, group))
    return {"groups": groups, "threads": int(params["senders"]),
            "deadline_s": float(seconds) + float(params["grace_s"])}


def metrics(obs: dict) -> dict:
    """Due -> bind seen, over every pod due in the window. A pod never
    bound enters at the moment the run gave up on it: a floor on its
    latency."""
    samples = [(obs["deadline_s"] if t is None else t) - due
               for due, t in zip(obs["due"], obs["bound"])]
    if not samples:
        return {}
    return {"bind_p50_s": percentile(samples, 0.50),
            "bind_p99_s": percentile(samples, 0.99)}
