"""The one place the yardstick touches the system under test: how the
scheduler is built and warmed, and which of its spans, counters and
verdicts are read. Everything else in this directory works on plain data.
jax and the program are imported inside the functions: the apiserver,
watcher and sender processes import this package and must never reach for
the chip."""

from __future__ import annotations

import re
import time

# LOOP_ERRORS sites at which a device program's answer was replaced by a
# fallback's (copied from benchmarks/connected.DEVICE_ERROR_SITES)
DEVICE_ERROR_SITES = ("device_drain", "device_gang", "device_preempt",
                      "drain_resolve", "resolver", "resolver_wait",
                      "drain_ready", "warm_patch")

# attempts the scheduler called unschedulable (set-up waits on it for a
# configuration's pending pods)
UNSCHEDULABLE = 'scheduler_schedule_attempts_total{result="unschedulable"}'

_SERIES = re.compile(r"^([^#\s]+)\s+(\S+)$")


def boot() -> dict:
    """Place the compile cache and name the device. -> the contract's
    ``device`` block, without the memory peak."""
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it
    (0 where it reports nothing, as the CPU backend does)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def _shape(x):
    """The structure of an object without its values."""
    if isinstance(x, dict):
        return tuple((k, _shape(v)) for k, v in sorted(x.items()))
    if isinstance(x, list):
        return tuple(_shape(v) for v in x)
    return None


def widest_first(pods: list) -> list:
    """One pod of every distinct spec shape first, then the rest in order:
    the warm ladder sizes its pod-batch rows from the head of the sample,
    and a shape it has not seen rebuilds the resident context later."""
    seen, head, tail = set(), [], []
    for p in pods:
        sig = _shape(p["spec"])
        (tail if sig in seen else head).append(p)
        seen.add(sig)
    return head + tail


def start_scheduler(url: str, scheduler_cfg: dict, sample: list,
                    headroom: int):
    """The deployment's scheduler, in this process: informers synced, the
    warm ladder run against the runner's own cache with the existing-pod
    bucket sized for ``headroom`` pods, then the loop started — a
    deployment's scheduler is running when pods arrive. ``sample`` is the
    cell's pods as plain objects. Raises when the resident context does
    not arm: a window that compiles and stages inside itself is another
    thing, not a slower run of the same."""
    from kubernetes_tpu.api import Pod
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.sched.runner import SchedulerRunner
    runner = SchedulerRunner(HTTPClient(url),
                             SchedulerConfiguration.from_dict(scheduler_cfg))
    runner.start(start_loop=False)
    try:
        if not runner.scheduler.warm_drain(
                [Pod.from_dict(p) for p in widest_first(sample)],
                slot_headroom=headroom):
            raise RuntimeError("warm_drain did not arm the resident drain "
                               "context")
        runner.start_loop()
    except BaseException:
        runner.stop()
        raise
    return runner


def open_window() -> None:
    """Spans from here on belong to the window."""
    from kubernetes_tpu.utils.tracing import TRACER
    TRACER.max_spans = 400_000
    TRACER.reset()


def window_spans() -> list:
    """[(name, start, end)] on the ``time.time()`` clock."""
    from kubernetes_tpu.utils.tracing import TRACER
    return [(s.name, s.start, s.end) for s in TRACER.spans()]


def counters(runner) -> dict:
    """Every series of the program's Prometheus exposition by its exposed
    name (labels included), plus the two sets of counts it keeps outside
    the registry: the resident context's (``ctx.<key>``) and the compile
    meter's (``compile.<key>``; ``compile.real`` is backend compiles less
    persistent-cache hits). Diff two of these for a window."""
    from kubernetes_tpu.metrics.registry import REGISTRY
    from kubernetes_tpu.parallel.aot import compile_meter
    out: dict = {}
    for line in REGISTRY.expose_text().splitlines():
        m = _SERIES.match(line)
        if m:
            try:
                out[m.group(1)] = float(m.group(2))
            except ValueError:
                pass
    for k, v in runner.scheduler.ctx_stats.items():
        if isinstance(v, (int, float)):
            out[f"ctx.{k}"] = float(v)
    for why, n in runner.scheduler.ctx_stats.get("reasons", {}).items():
        out[f"ctx.reason.{why}"] = float(n)
    snap = compile_meter().snapshot()
    out["compile.backend"] = float(snap["backendCompiles"])
    out["compile.hits"] = float(snap["cacheHits"])
    out["compile.real"] = float(snap["backendCompiles"] - snap["cacheHits"])
    return out


def delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def pending(runner) -> int:
    """Pods in the scheduling queue right now (active, backoff and
    unschedulable), from the gauges the scheduler sets at every pop."""
    from kubernetes_tpu.metrics.registry import QUEUE_DEPTH
    return int(sum(QUEUE_DEPTH.get({"queue": q})
                   for q in ("active", "backoff", "unschedulable")))


def residency(runner) -> dict:
    """Whether the resident drain context is armed, and on which platforms
    its arrays live. Read off the shardings: the loop may still be running
    (a burst cut by its deadline) and donating these very buffers."""
    import jax
    ctx = runner.scheduler._drain_ctx
    if ctx is None:
        return {"armed": False, "platforms": []}
    devs = {d for leaf in jax.tree_util.tree_leaves(ctx["ct"])
            for d in leaf.sharding.device_set}
    return {"armed": True,
            "platforms": sorted({d.platform for d in devs})}


def settle(runner) -> dict:
    """The program's own judges, after the window: the auditor (stopped,
    then two settle sweeps — its confirm-twice invariants need consecutive
    observations) and the parity sentinel (every submitted sample
    judged)."""
    auditor = runner.auditor
    auditor.stop()
    for _ in range(2):
        auditor.run_once()
    out = {"violations": int(auditor.total_violations), "parity": None}
    sentinel = runner.scheduler.sentinel
    if sentinel is not None:
        sentinel.drain(timeout=120.0)
        out["parity"] = sentinel.stats()
    return out


def resilience(runner) -> dict:
    b = runner.scheduler.breaker
    return {"degradedIndex": int(b.index), "degradedMode": b.mode,
            "breakerTrips": int(b.trips),
            "tripReasons": dict(b.trip_reasons)}


def client(url: str, timeout: float = 120.0):
    from kubernetes_tpu.client.clientset import HTTPClient
    return HTTPClient(url, timeout=timeout)


def wait_until(cond, timeout: float, every: float = 0.02) -> bool:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(every)
    return bool(cond())
