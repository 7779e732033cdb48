"""Every bound pod's ``spec.nodeSelector`` is a subset of its node's
labels."""

from . import bound_by_node, key


def check(nodes, pods) -> list:
    problems = []
    placed = bound_by_node(pods)
    for n in nodes:
        labels = n["metadata"].get("labels") or {}
        for p in placed.get(n["metadata"]["name"], []):
            want = p["spec"].get("nodeSelector") or {}
            wrong = {k: v for k, v in want.items() if labels.get(k) != v}
            if wrong:
                problems.append(f"{key(p)} on {n['metadata']['name']} wants "
                                f"{wrong}, node has "
                                f"{ {k: labels.get(k) for k in wrong} }")
    return problems
