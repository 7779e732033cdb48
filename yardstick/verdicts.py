"""What ``correct`` means: answers, and who gave them. Nothing here looks
at how many pods were bound, how many drains ran, whether the context was
rebuilt, whether anything compiled, or what was logged — unfinished work is
``failed``, and pace and regime are counters beside the result.

Each verdict is (name, ok, detail). ``correct`` is true when every one is
ok."""

from __future__ import annotations

from . import program, reference


def read_back(seen: dict, listed: list) -> tuple:
    """(a) every bind the watcher saw is read back from the apiserver
    after the window with the same node. -> (verdict, keys not
    confirmed)."""
    stored = {reference.key(p): p.get("spec", {}).get("nodeName")
              for p in listed}
    wrong = {k: (node, stored.get(k)) for k, (_t, node) in seen.items()
             if stored.get(k) != node}
    detail = "; ".join(f"{k}: watch said {w!r}, list says {s!r}"
                       for k, (w, s) in sorted(wrong.items())[:5])
    return (("bind_read_back", not wrong,
             f"{len(wrong)} of {len(seen)} binds not confirmed: {detail}"
             if wrong else f"{len(seen)} binds confirmed"), set(wrong))


def end_state(kinds, nodes: list, pods: list) -> list:
    """(b) all bound pods on all nodes pass the plain reference, one
    verdict a constraint kind the generator declares."""
    out = []
    for kind, check in reference.load(kinds).items():
        problems = check(nodes, pods)
        out.append((f"end_state.{kind}", not problems,
                    f"{len(problems)} violation(s): "
                    + "; ".join(problems[:5]) if problems else "valid"))
    return out


def own_judges(settled: dict) -> list:
    """(c) the program's own judges agree."""
    out = [("auditor", settled["violations"] == 0,
            f"{settled['violations']} invariant violation(s) after two "
            "settle sweeps")]
    par = settled.get("parity")
    if par is not None:
        ok = par.get("divergences") == 0 and par.get("pending") == 0
        out.append(("sentinel", ok,
                    f"divergences {par.get('divergences')!r}, pending "
                    f"{par.get('pending')!r}, samples {par.get('samples')}, "
                    f"last {par.get('lastDivergence')}"))
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
            "; ".join(problems) or f"resident context on {platform}")
