"""Device-layer chaos — XLA-style failures at the program entry points.

Wraps the three device program entries the connected loop dispatches —
``gang_schedule`` (per-batch path), ``drain_step`` (fused drain), and
``preempt_wave`` (preemption storm) — so scheduled cycles raise
compile/runtime errors the way a miscompiling jaxlib or a lost device
does (the ROADMAP's virtual-CPU GSPMD miscompiles are the live
precedent). The scheduler's circuit breaker is the consumer: enough
consecutive device failures must degrade mesh -> single-device -> the
pure-numpy oracle instead of killing the loop.

Install/uninstall patch module attributes; the scheduler resolves all
three names at call time (function-level import or module-attr call), so
no product changes are needed for the injection itself.
"""

from __future__ import annotations

from kubernetes_tpu.chaos.hooks import ChaosDeviceError
from kubernetes_tpu.chaos.schedule import FaultSchedule

# (site, module path, attribute) triples patched by install()
_SEAMS = (
    ("device.gang", "kubernetes_tpu.models.gang", "gang_schedule"),
    ("device.gang", "kubernetes_tpu.sched.scheduler", "gang_schedule"),
    ("device.drain", "kubernetes_tpu.models.gang", "drain_step"),
    ("device.preempt", "kubernetes_tpu.sched.preemption", "preempt_wave"),
)


class DeviceChaos:
    """Context manager (or explicit install/uninstall) for device faults."""

    def __init__(self, schedule: FaultSchedule):
        self.schedule = schedule
        self._saved: list[tuple] = []

    def _wrap(self, site: str, fn):
        schedule = self.schedule

        def chaotic(*a, **kw):
            f = schedule.should_fire(site)
            if f is not None:
                name = ("UNIMPLEMENTED: chaos compile failure"
                        if f.kind == "compile"
                        else "INTERNAL: chaos device execution failure")
                raise ChaosDeviceError(
                    f"{name} at {site} op {f.at} (seed {schedule.seed})")
            out = fn(*a, **kw)
            schedule.note_ok(site)
            return out
        chaotic.__wrapped__ = fn
        return chaotic

    def install(self) -> "DeviceChaos":
        import importlib
        if self._saved:
            return self
        for site, mod_path, attr in _SEAMS:
            mod = importlib.import_module(mod_path)
            orig = getattr(mod, attr)
            self._saved.append((mod, attr, orig))
            setattr(mod, attr, self._wrap(site, orig))
        return self

    def uninstall(self) -> None:
        for mod, attr, orig in self._saved:
            setattr(mod, attr, orig)
        self._saved = []

    def __enter__(self) -> "DeviceChaos":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()
