"""Required topology spread (``whenUnsatisfiable: DoNotSchedule``), written
from upstream's PodTopologySpread filter. That filter admits a pod to a
node of domain d when, over the bound pods its selector matches in its own
namespace, counted by the value of the topology key on their nodes,
``count[d] + 1 - min(count) <= maxSkew`` (the + 1 is the pod itself, which
matches its own selector here); the minimum runs over every domain that has
a node, empty ones included, and a node without the key takes no such pod.

This file sees the end state only, and in a window that deletes nothing
that is enough: counts only grow, so the last pod placed in the fullest
domain passed ``count + 1 - min <= maxSkew`` with the count the domain ends
with, and the minimum only rose afterwards. So for every bound pod's every
such constraint, max - min of the counts over the domains that have a node
is at most ``maxSkew``. The argument needs every counted pod to have been
admitted under that same rule, so a matched pod that does not carry the
constraint is refused, as is what this file cannot judge: ``minDomains``,
``matchLabelKeys``, a ``nodeAffinityPolicy`` or ``nodeTaintsPolicy`` other
than the default, a pod that narrows its nodes by selector or affinity
(which would shrink the set of domains). Pods without ``nodeName`` are not
counted."""

from . import key
from .antiaffinity import _selects

_KNOWN = {"maxSkew", "topologyKey", "whenUnsatisfiable", "labelSelector",
          "nodeAffinityPolicy", "nodeTaintsPolicy"}
_DEFAULT_POLICY = {"nodeAffinityPolicy": "Honor", "nodeTaintsPolicy": "Ignore"}


def _hard(pod) -> list:
    return [c for c in pod["spec"].get("topologySpreadConstraints") or []
            if c.get("whenUnsatisfiable", "DoNotSchedule") == "DoNotSchedule"]


def _rule(pod, c) -> tuple:
    """What a constraint asks, comparable between pods; raises on what this
    file cannot judge."""
    extra = set(c) - _KNOWN
    if extra:
        raise ValueError(f"topologyspread.py cannot judge {sorted(extra)} "
                         f"on {key(pod)}")
    for field, default in _DEFAULT_POLICY.items():
        if c.get(field, default) != default:
            raise ValueError(f"topologyspread.py cannot judge {field} "
                             f"{c[field]!r} on {key(pod)}")
    spec = pod["spec"]
    if spec.get("nodeSelector") or (spec.get("affinity") or {}).get(
            "nodeAffinity"):
        raise ValueError(f"topologyspread.py counts every node that carries "
                         f"the key; {key(pod)} narrows them by node "
                         "selector or node affinity")
    return (pod["metadata"].get("namespace", "default"), c["topologyKey"],
            int(c["maxSkew"]),
            repr(sorted((c.get("labelSelector") or {}).items())))


def check(nodes, pods) -> list:
    node_labels = {n["metadata"]["name"]: n["metadata"].get("labels") or {}
                   for n in nodes}
    bound = [p for p in pods if p["spec"].get("nodeName") in node_labels]
    rules: dict = {}  # rule -> (its selector, the bound pods that carry it)
    for p in bound:
        for c in _hard(p):
            rules.setdefault(_rule(p, c), (c.get("labelSelector") or {},
                                           []))[1].append(p)
    problems = []
    for rule, (selector, carriers) in rules.items():
        ns, tk, max_skew, _ = rule
        carrying = {id(p) for p in carriers}
        counts = {labels[tk]: 0 for labels in node_labels.values()
                  if tk in labels}
        for p in carriers:
            if tk not in node_labels[p["spec"]["nodeName"]]:
                problems.append(f"{key(p)} must spread over {tk}, its node "
                                f"{p['spec']['nodeName']} has no such label")
        for p in bound:
            domain = node_labels[p["spec"]["nodeName"]].get(tk)
            if (domain is None
                    or p["metadata"].get("namespace", "default") != ns
                    or not _selects(selector,
                                    p["metadata"].get("labels") or {})):
                continue
            if id(p) not in carrying:
                raise ValueError(
                    f"topologyspread.py cannot tell from an end state "
                    f"whether {key(p)} was placed within maxSkew "
                    f"{max_skew} over {tk}: the selector matches it and it "
                    "does not carry the constraint")
            counts[domain] += 1
        if counts and max(counts.values()) - min(counts.values()) > max_skew:
            problems.append(
                f"{len(carriers)} pod(s) of {ns} ask for maxSkew {max_skew} "
                f"over {tk}; the pods they select stand at "
                f"{dict(sorted(counts.items()))}")
    return problems
