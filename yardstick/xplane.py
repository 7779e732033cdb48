"""From a profiler trace (.xplane.pb) to numbers: device busy and idle time
in a window, device time by XLA module, the operations that took most
time, and the longest idle gaps named after what the host was doing.

Read with nothing but ``jax.profiler.ProfileData``. What a TPU trace is
expected to hold (PERF.md section 3 says how far this was seen on the
chip): one plane a chip named ``/device:TPU:<n>``; on it the line
``XLA Modules`` has one event for each execution of a compiled program,
named ``jit_<function>(<fingerprint>)``, and the line ``XLA Ops`` one event
for each operation inside them. Host threads are lines of the plane
``/host:CPU``; a ``TraceAnnotation`` shows there under its own name with its
keyword arguments as stats. Where a line or the markers are not found the
reduction falls back to what is there (any line of the device plane; the
span of the device's events) and says so under ``notes``, so a renamed line
costs detail and not the run.
"""

from __future__ import annotations

import glob
import os
import re

# Published peaks of one chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e". A kind that is not here is an
# error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
MARK_START = "yardstick/window_start"
MARK_END = "yardstick/window_end"
OUTSIDE = "outside-spans"


class NoDevicePlane(ValueError):
    """The trace holds no accelerator plane."""


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks on record for device kind {device_kind!r}")
    return PEAKS[device_kind]


def newest_trace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def module_name(event_name: str) -> str:
    """``jit_drain_step(123456)`` -> ``jit_drain_step``."""
    return event_name.split("(", 1)[0]


def union(intervals: list) -> list:
    """Sorted, disjoint [start, end] covering the same points."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def subtract(interval: list, covered: list) -> list:
    """The parts of ``interval`` outside the disjoint, sorted ``covered``."""
    out, (s, e) = [], interval
    for cs, ce in covered:
        if ce <= s or cs >= e:
            continue
        if cs > s:
            out.append([s, cs])
        s = max(s, ce)
    if s < e:
        out.append([s, e])
    return out


def name_gap(gap: list, spans: list) -> str:
    """The span that accounts for most of ``gap``, each instant going to
    the shortest span that covers it (a child before its parent);
    ``outside-spans`` when no span holds the largest part."""
    share: dict = {}
    covered: list = []
    for name, s, e in sorted(spans, key=lambda x: x[2] - x[1]):
        inside = clip([[s, e]], gap[0], gap[1])
        if not inside:
            continue
        fresh = subtract(inside[0], covered)
        if fresh:
            share[name] = share.get(name, 0.0) + total(fresh)
            covered = union(covered + fresh)
    share[OUTSIDE] = (gap[1] - gap[0]) - total(covered)
    return max(share, key=share.get)


def _events(plane, line_name: str | None) -> list:
    """Events of the line with that name; of every line for None."""
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9)
            for line in plane.lines
            if line_name is None or line.name == line_name
            for ev in line.events]


def _marks(planes) -> dict:
    """{marker name: (trace-clock seconds, time.time() seconds)}."""
    out: dict = {}
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (MARK_START, MARK_END):
                    stats = dict(ev.stats)
                    out[ev.name] = (ev.start_ns * 1e-9,
                                    float(stats.get("t", "nan")))
    return out


def describe(path: str, limit: int = 6) -> list:
    """Planes, lines and a few event names: for reading a trace by hand."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name}: {len(evs)} events")
            for ev in evs[:limit]:
                out.append(f"    {ev.name} start_ns={ev.start_ns} "
                           f"duration_ns={ev.duration_ns} "
                           f"stats={dict(ev.stats)}")
    return out


def reduce(path: str, spans: list | None = None, top: int = 10) -> dict:
    """``spans`` is [(name, start, end)] on the ``time.time()`` clock; the
    window markers carry that clock's reading, which ties the two.

    -> {"window_s", "busy_s", "idle_share", "chips", "notes",
        "modules": {name: {"s": device seconds, "n": executions}},
        "device_ops": [[name, seconds], ...], "idle_gaps": [[name,
        seconds], ...]} — busy and module time averaged over the chips,
    everything clipped to the window between the two markers."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise NoDevicePlane(f"{path}: no /device:TPU:<n> plane "
                            f"({[p.name for p in planes]})")
    marks, notes = _marks(planes), []
    per_chip = []
    for plane in devices:
        ops = _events(plane, OP_LINE)
        mods = _events(plane, MODULE_LINE)
        if not ops:
            notes.append(f"{plane.name}: no {OP_LINE!r} line among "
                         f"{[ln.name for ln in plane.lines]}")
            ops = mods or _events(plane, None)
        per_chip.append((ops, mods))
    if MARK_START in marks and MARK_END in marks:
        lo, hi = marks[MARK_START][0], marks[MARK_END][0]
        offset = marks[MARK_START][0] - marks[MARK_START][1]
    else:
        every = [iv for ops, _ in per_chip for iv in ops]
        if not every:
            raise ValueError(f"{path}: no device event and no marker")
        notes.append("window markers not found: window is the span of the "
                     "device's events, gaps are not named")
        lo, hi = min(s for _, s, _ in every), max(e for _, _, e in every)
        offset, spans = 0.0, []
    busy, modules, op_time = [], {}, {}
    for ops, mods in per_chip:
        busy.append(union(clip([[s, e] for _, s, e in ops], lo, hi)))
        for name, s, e in mods:
            for cs, ce in clip([[s, e]], lo, hi):
                m = modules.setdefault(module_name(name),
                                       {"s": 0.0, "n": 0})
                m["s"] += (ce - cs) / len(devices)
                m["n"] += 1
        for name, s, e in ops:
            for cs, ce in clip([[s, e]], lo, hi):
                op_time[name] = op_time.get(name, 0.0) + (
                    ce - cs) / len(devices)
    busy_s = sum(total(b) for b in busy) / len(devices)
    gaps = sorted(subtract([lo, hi], busy[0]), key=lambda g: g[0] - g[1])
    on_trace_clock = [(n, s + offset, e + offset) for n, s, e in spans or []]
    return {
        "window_s": hi - lo, "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (hi - lo),
        "chips": len(devices), "modules": modules, "notes": notes,
        "device_ops": [[n, s] for n, s in sorted(
            op_time.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[name_gap(g, on_trace_clock), g[1] - g[0]]
                      for g in gaps[:top]],
    }
