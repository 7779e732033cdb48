"""Self time in the one tracer (utils/tracing.Tracer): a span's wall and
blocked time less what its child spans cover, summed by name beside the
blocked totals and exposed as two series (metrics/registry._span_lines)."""

import time

import pytest

from kubernetes_tpu.metrics.registry import REGISTRY
from kubernetes_tpu.utils.tracing import TRACER, Tracer

SLEEP = 0.03
SPIN = 0.01
# what a thread that spins may still lose to the OS, and a sleep may
# overshoot by: two 100 Hz scheduler ticks
SLACK = 0.02


def spin(seconds: float) -> None:
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


def sleep_around_spin(tr):
    with tr.span("parent"):
        time.sleep(SLEEP)
        with tr.span("child"):
            spin(SPIN)
        time.sleep(SLEEP)
    # (self blocked low, high) a name, in seconds
    return {"parent": (2 * SLEEP - 1e-3, 2 * SLEEP + SLACK),
            "child": (0.0, SLACK)}


def spin_around_sleep(tr):
    with tr.span("parent"):
        spin(SPIN)
        with tr.span("child"):
            time.sleep(SLEEP)
        spin(SPIN)
    return {"parent": (0.0, SLACK),
            "child": (SLEEP - 1e-3, SLEEP + SLACK)}


def discarded_child(tr):
    with tr.span("parent"):
        spin(SPIN)
        with tr.span("child") as sp:
            sp.discard = True
            time.sleep(SLEEP)
    # the discarded span adds nothing anywhere: its sleep stays in the
    # parent's own time
    return {"parent": (SLEEP - 1e-3, SLEEP + SLACK)}


def unsampled_child(tr):
    tr.ratio = 0.5
    with tr.span("skipped"):  # the 1st span of a 1-in-2 tracer is not kept
        pass
    with tr.span("parent"):
        spin(SPIN)
        with tr.span("child") as sp:
            assert sp is None  # not sampled: on no stack
            time.sleep(SLEEP)
    return {"parent": (SLEEP - 1e-3, SLEEP + SLACK)}


def measure(case):
    """One run of ``case`` on a fresh tracer. -> (tracer, spans, self
    totals, self blocked totals, whether every self blocked total lies
    under its upper bound). The sums' arithmetic and the lower bounds are
    asserted on every run; the upper bounds only say whether the OS left
    the spinning thread alone this time."""
    tr = Tracer()
    want = case(tr)
    spans = tr.spans()
    own, own_blocked = tr.self_totals()
    assert set(own) == set(own_blocked) == set(want)
    assert {sp.name for sp in spans} == set(want)
    inside = True
    for sp in spans:
        kids = [c for c in spans if c.parent_id == sp.span_id]
        wall = sp.end - sp.start
        kids_wall = sum(c.end - c.start for c in kids)
        kids_cpu = sum(c.cpu_s for c in kids)
        assert own[sp.name] == pytest.approx(wall - kids_wall, abs=1e-6)
        # self blocked is the span's blocked time less its children's
        assert own_blocked[sp.name] == pytest.approx(max(
            sp.blocked_s - sum(c.blocked_s for c in kids), 0.0), abs=1e-6)
        assert own_blocked[sp.name] == pytest.approx(
            max(wall - kids_wall - (sp.cpu_s - kids_cpu), 0.0), abs=1e-6)
        assert own_blocked[sp.name] <= own[sp.name] + 1e-9
        low, high = want[sp.name]
        # a sleep is never on a CPU: the lower bounds hold on every run
        assert own_blocked[sp.name] >= low, (sp.name, own_blocked)
        inside = inside and own_blocked[sp.name] <= high
    return tr, spans, own, own_blocked, inside


@pytest.mark.parametrize("case", [sleep_around_spin, spin_around_sleep,
                                  discarded_child, unsampled_child])
def test_self_time_is_the_span_less_its_children(case):
    # blocked self time follows the sleep, not the spin: within SLACK of
    # it on a run the OS did not take the spinning thread off its CPU (a
    # loaded box may, so a few runs are allowed to find such a one)
    for _ in range(40):
        tr, spans, own, own_blocked, inside = measure(case)
        if inside:
            break
    assert inside, own_blocked
    # the blocked totals are the whole span's, children included
    blocked = tr.blocked_totals()
    for sp in spans:
        assert blocked[sp.name] == pytest.approx(sp.blocked_s, abs=1e-9)
    # reset empties the ring, not the sums (readers diff two reads)
    tr.reset()
    assert tr.spans() == [] and tr.self_totals() == (own, own_blocked)


def test_both_self_series_are_exposed_by_span_name():
    with TRACER.span("test/self_exposed"):
        with TRACER.span("test/self_exposed_child"):
            time.sleep(0.01)
    text = REGISTRY.expose_text()
    for series in ("scheduler_span_self_seconds_total",
                   "scheduler_span_self_blocked_seconds_total"):
        assert f"# TYPE {series} counter" in text
        for name in ("test/self_exposed", "test/self_exposed_child"):
            line = f'{series}{{span="{name}"}} '
            assert line in text, (series, name)
    own, _ = TRACER.self_totals()
    value = float(text.split(
        'scheduler_span_self_seconds_total{span="test/self_exposed_child"} '
    )[1].split()[0])
    assert value == pytest.approx(own["test/self_exposed_child"])
    assert value >= 0.01 - 1e-3
    assert "scheduler_gang_batch_duration_seconds" not in text
