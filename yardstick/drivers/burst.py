"""Closed, fixed work: the configuration's ``measurePods`` created at once
by ``concurrency`` bulk creates of ``chunk`` pods, scheduler already
running. The run ends when the last pod is bound or at the deadline,
``--seconds`` after the first create."""

E2E = {"bound_rate": "pods/s"}


def plan(params: dict, config: dict, seed: int, seconds: float) -> dict:
    n, chunk = int(config["measurePods"]), int(params["chunk"])
    return {"groups": [(0.0, min(chunk, n - i)) for i in range(0, n, chunk)],
            "threads": int(params["concurrency"]),
            "deadline_s": float(seconds)}


def metrics(obs: dict) -> dict:
    """Pods bound over the time from the first create to the last bind
    seen; where the deadline cut the burst, over ``--seconds``."""
    times = [t for t in obs["bound"] if t is not None]
    if not times:
        return {"bound_rate": 0.0}
    cut = len(times) < len(obs["bound"])
    return {"bound_rate": len(times) / (obs["seconds"] if cut
                                        else max(times))}
