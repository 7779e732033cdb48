"""Required pod anti-affinity: no bound pod shares a topology domain with
another bound pod that one of its required terms selects, in the term's
namespaces (the pod's own when the term names none). Checked from every
pod's side, so the order in which two pods arrived does not matter."""

from . import key

_REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
_KNOWN = {"topologyKey", "labelSelector", "namespaces"}


def _selects(selector: dict, labels: dict) -> bool:
    for k, v in (selector.get("matchLabels") or {}).items():
        if labels.get(k) != v:
            return False
    for e in selector.get("matchExpressions") or []:
        k, op, vals = e["key"], e["operator"], e.get("values") or []
        if op == "In":
            ok = labels.get(k) in vals
        elif op == "NotIn":
            ok = labels.get(k) not in vals
        elif op == "Exists":
            ok = k in labels
        elif op == "DoesNotExist":
            ok = k not in labels
        else:
            raise ValueError(f"selector operator {op!r}")
        if not ok:
            return False
    return True


def check(nodes, pods) -> list:
    node_labels = {n["metadata"]["name"]: n["metadata"].get("labels") or {}
                   for n in nodes}
    bound = [p for p in pods if p["spec"].get("nodeName") in node_labels]
    terms = []  # (pod, term) for every required anti-affinity term
    for p in bound:
        anti = (p["spec"].get("affinity") or {}).get("podAntiAffinity") or {}
        for term in anti.get(_REQUIRED) or []:
            extra = set(term) - _KNOWN
            if extra:
                raise ValueError(f"antiaffinity.py cannot judge {extra} on "
                                 f"{key(p)}")
            terms.append((p, term))
    domains: dict = {}  # topology key -> domain value -> pods there
    for tk in {t["topologyKey"] for _, t in terms}:
        by_value = domains.setdefault(tk, {})
        for p in bound:
            value = node_labels[p["spec"]["nodeName"]].get(tk)
            if value is not None:
                by_value.setdefault(value, []).append(p)
    problems = []
    for p, term in terms:
        tk = term["topologyKey"]
        value = node_labels[p["spec"]["nodeName"]].get(tk)
        if value is None:
            continue
        spaces = term.get("namespaces") or [
            p["metadata"].get("namespace", "default")]
        for other in domains[tk][value]:
            if other is p or other["metadata"].get(
                    "namespace", "default") not in spaces:
                continue
            if _selects(term.get("labelSelector") or {},
                        other["metadata"].get("labels") or {}):
                problems.append(f"{key(p)} repels {key(other)} on {tk}="
                                f"{value}, both are there")
    return problems
