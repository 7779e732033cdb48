"""The plain reference: validity of an end state, one file a constraint
kind. Each kind file has ``check(nodes, pods) -> list[str]`` over the node
and pod objects as the apiserver lists them (plain dicts); every bound pod
is checked, not a sample. A kind that has to know who left (a preemption's
victims) declares ``check(nodes, pods, gone)`` and is also given the last
objects of the pods deleted since the watch began. Nothing here imports
the program."""

import importlib
import inspect


def load(kinds) -> dict:
    """{kind: check} for the constraint kinds a generator declares. A kind
    with no file is refused: a deployment may not emit a hard constraint
    that nothing judges."""
    checks = {}
    for kind in kinds:
        try:
            mod = importlib.import_module(f"{__name__}.{kind}")
        except ModuleNotFoundError as e:
            raise SystemExit(
                f"constraint kind {kind!r} has no yardstick/reference/"
                f"{kind}.py: add the file with the generator") from e
        checks[kind] = mod.check
    return checks


def takes_gone(check) -> bool:
    """Whether a kind's ``check`` declares the third parameter."""
    return len(inspect.signature(check).parameters) >= 3


def bound_by_node(pods) -> dict:
    """{node name: [pod, ...]} over the pods that carry spec.nodeName."""
    out: dict = {}
    for p in pods:
        name = p.get("spec", {}).get("nodeName")
        if name:
            out.setdefault(name, []).append(p)
    return out


def key(pod) -> str:
    meta = pod["metadata"]
    return f"{meta.get('namespace', 'default')}/{meta['name']}"
