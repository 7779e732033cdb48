"""Every bound pod tolerates each NoSchedule taint of its node."""

from . import bound_by_node, key


def _tolerates(tol: dict, taint: dict) -> bool:
    if tol.get("effect") and tol["effect"] != taint.get("effect"):
        return False
    op = tol.get("operator") or "Equal"
    if not tol.get("key"):
        return op == "Exists"
    if tol["key"] != taint.get("key"):
        return False
    return op == "Exists" or (tol.get("value") or "") == (
        taint.get("value") or "")


def check(nodes, pods) -> list:
    problems = []
    placed = bound_by_node(pods)
    for n in nodes:
        taints = [t for t in (n.get("spec", {}).get("taints") or [])
                  if t.get("effect") == "NoSchedule"]
        if not taints:
            continue
        for p in placed.get(n["metadata"]["name"], []):
            tols = p["spec"].get("tolerations") or []
            for t in taints:
                if not any(_tolerates(tol, t) for tol in tols):
                    problems.append(
                        f"{key(p)} on {n['metadata']['name']} does not "
                        f"tolerate {t.get('key')}={t.get('value')}:"
                        "NoSchedule")
    return problems
