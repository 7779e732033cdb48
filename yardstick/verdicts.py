"""What ``correct`` means: answers, and who gave them. Nothing here looks
at how many pods were bound, how many drains ran, whether the context was
rebuilt, whether anything compiled, or what was logged — unfinished work is
``failed``, and pace and regime are counters beside the result.

Each verdict is (name, ok, detail, n): ``n`` is the number it compared,
a count of what is wrong, and its limit is 0. ``correct`` is true when
every one is ok."""

from __future__ import annotations

from . import program, reference


def _stored(listed: list) -> dict:
    """{key: node name or None} of the pods as the apiserver lists them."""
    return {reference.key(p): p.get("spec", {}).get("nodeName")
            for p in listed}


def read_back(seen: dict, listed: list, gone=None,
              may_leave=frozenset()) -> tuple:
    """(a) every bind the watcher saw is read back from the apiserver
    after the window with the same node. A pod that is no longer listed
    confirms its bind only where the configuration lets it leave
    (``may_leave``: keys of the phases under ``leavers``) and the watcher
    saw it ``DELETED`` after that bind (``gone``: {key: [t, last
    object]}). -> (verdict, keys not confirmed)."""
    stored = _stored(listed)
    gone = gone or {}
    wrong = {}
    for k, (t, node) in seen.items():
        if k in stored:
            if stored[k] != node:
                wrong[k] = (node, stored[k])
        elif not (k in may_leave and k in gone and gone[k][0] >= t):
            wrong[k] = (node, None)
    detail = "; ".join(f"{k}: watch said {w!r}, list says {s!r}"
                       for k, (w, s) in sorted(wrong.items())[:5])
    return (("bind_read_back", not wrong,
             f"{len(wrong)} of {len(seen)} binds not confirmed: {detail}"
             if wrong else f"{len(seen)} binds confirmed", len(wrong)),
            set(wrong))


def left_pending(pending: list, listed: list, seen: dict) -> tuple:
    """Every pod the configuration states fits nowhere (its pending
    phase, by key) is listed after the window without a node, and the
    watcher saw no bind of it."""
    stored = _stored(listed)
    wrong = [f"{k}: not listed" if k not in stored else
             f"{k}: bound to {stored[k] or seen[k][1]!r}"
             for k in pending
             if k not in stored or stored[k] or k in seen]
    return ("left_pending", not wrong,
            f"{len(wrong)} of {len(pending)} pods the configuration states "
            f"fit nowhere: {'; '.join(wrong[:5])}" if wrong
            else f"{len(pending)} pods left pending", len(wrong))


def end_state(kinds, nodes: list, pods: list, gone=()) -> list:
    """(b) all bound pods on all nodes pass the plain reference, one
    verdict a constraint kind the generator declares. ``gone``: the last
    objects of the pods deleted since the watch began, for a kind whose
    ``check`` takes them."""
    out = []
    for kind, check in reference.load(kinds).items():
        problems = (check(nodes, pods, list(gone))
                    if reference.takes_gone(check) else check(nodes, pods))
        out.append((f"end_state.{kind}", not problems,
                    f"{len(problems)} violation(s): "
                    + "; ".join(problems[:5]) if problems else "valid",
                    len(problems)))
    return out


def own_judges(settled: dict) -> list:
    """(c) the program's own judges agree."""
    out = [("auditor", settled["violations"] == 0,
            f"{settled['violations']} invariant violation(s) after two "
            "settle sweeps", settled["violations"])]
    par = settled.get("parity")
    if par is not None:
        wrong = [par.get("divergences"), par.get("pending")]
        ok = wrong == [0, 0]
        out.append(("sentinel", ok,
                    f"divergences {wrong[0]!r}, pending {wrong[1]!r}, "
                    f"samples {par.get('samples')}, "
                    f"last {par.get('lastDivergence')}",
                    sum(w or 0 for w in wrong) or int(not ok)))
    return out


def device_answers(platform: str, residency: dict, resilience: dict,
                   counters: dict) -> tuple:
    """(d) the device gave the answers: resident context armed on
    ``platform``, no degraded mode, no breaker trip, no loop error at a
    site where a fallback replaces a device program's answer."""
    problems = []
    if not residency.get("armed") or residency.get("platforms") != [
            platform]:
        problems.append(f"resident context not armed on {platform!r}: "
                        f"{residency}")
    if resilience.get("degradedIndex") != 0:
        problems.append(f"degraded mode {resilience.get('degradedMode')!r}")
    if resilience.get("breakerTrips") != 0:
        problems.append(f"breaker trips {resilience.get('breakerTrips')!r} "
                        f"({resilience.get('tripReasons')})")
    for site in program.DEVICE_ERROR_SITES:
        n = counters.get(f'scheduler_loop_errors_total{{site="{site}"}}', 0)
        if n:
            problems.append(f"loop error at {site}: {n:g}")
    return ("device_answers", not problems,
            "; ".join(problems) or f"resident context on {platform}",
            len(problems))
