"""Per node: the summed requests of its pods (cpu, memory, any other
requested resource) and their count stay within ``status.allocatable``."""

from . import bound_by_node
from ._quantity import amount


def _requests(pod) -> dict:
    spec = pod["spec"]
    if spec.get("initContainers") or spec.get("overhead"):
        raise ValueError("capacity.py sums plain containers only; "
                         f"{pod['metadata']['name']} has initContainers or "
                         "overhead")
    total: dict = {}
    for c in spec.get("containers", []):
        for res, q in ((c.get("resources") or {}).get("requests")
                       or {}).items():
            total[res] = total.get(res, 0) + amount(res, q)
    return total


def check(nodes, pods) -> list:
    problems = []
    placed = bound_by_node(pods)
    by_name = {n["metadata"]["name"]: n for n in nodes}
    for name, here in placed.items():
        node = by_name.get(name)
        if node is None:
            problems.append(f"{len(here)} pod(s) bound to {name}, which is "
                            "not a node")
            continue
        alloc = {r: amount(r, q)
                 for r, q in node["status"]["allocatable"].items()}
        used = {"pods": len(here)}
        for p in here:
            for res, q in _requests(p).items():
                used[res] = used.get(res, 0) + q
        for res, q in used.items():
            if q > alloc.get(res, 0):
                problems.append(f"{name}: {res} {q} requested by "
                                f"{len(here)} pods, {alloc.get(res, 0)} "
                                "allocatable")
    return problems
