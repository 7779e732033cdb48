"""The cyclic garbage collector's policy for the scheduler's process.

CPython's defaults, (700, 10, 10), suit a short script. A scheduler holds a
set-up heap that never dies (the decoded nodes, the informer stores, the
encodings, every compiled program's Python side) and then allocates a few
hundred containers a pod for as long as pods arrive: a young collection
every ~7 pod events, and now and then a full one that walks the whole heap
holding the interpreter lock, so every thread of the process stops.

One policy, owned by ``SchedulerRunner`` and held by the process while any
runner runs: a fixed young-generation threshold from ``start()`` on, before
set-up allocates, the set-up heap frozen into the permanent generation when
the runner's loop first starts, and both given back when the last runner
stops. The collector stays on — cycles are still reclaimed, later and in
larger young batches, and a full collection walks only what was allocated
after the freeze.
"""

from __future__ import annotations

import gc
import threading
import time

from kubernetes_tpu.metrics.registry import REGISTRY, series_lines

# Container allocations less deallocations between two young collections
# while a runner runs. Chosen on the chip from 10,000 / 50,000 / 200,000
# (PERF.md section 6, PR 29); generations 1 and 2 keep their ratios of 10.
YOUNG_THRESHOLD = 50_000


class GcPolicy:
    """Process-wide by nature, so one instance (``GC_POLICY``) counted by
    its holders; the totals below only ever grow."""

    def __init__(self):
        self._lock = threading.Lock()
        self._holders = 0
        self._found: tuple = ()  # thresholds the first holder found
        # written by the hook alone: the collector runs one collection at
        # a time, on whichever thread allocated across a threshold
        self.pause_ns = 0
        self.collections = [0, 0, 0]
        self._t0 = 0

    def acquire(self) -> None:
        """A runner is starting: from here on the young generation fills
        to ``YOUNG_THRESHOLD`` and every collection is counted."""
        with self._lock:
            self._holders += 1
            if self._holders == 1:
                self._found = gc.get_threshold()
                gc.set_threshold(YOUNG_THRESHOLD, *self._found[1:])
                gc.callbacks.append(self._on_gc)

    def freeze(self) -> None:
        """The set-up heap is complete (informers synced, nodes encoded,
        the warm ladder compiled): collect what set-up left unreachable,
        then move everything alive to the permanent generation, which no
        later collection walks."""
        gc.collect()
        gc.freeze()

    def release(self) -> None:
        """A runner stopped; the last one leaves the process as the first
        found it."""
        with self._lock:
            self._holders -= 1
            if self._holders:
                return
            gc.callbacks.remove(self._on_gc)
            gc.unfreeze()
            gc.set_threshold(*self._found)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter_ns()
        elif self._t0:
            self.pause_ns += time.perf_counter_ns() - self._t0
            self.collections[info["generation"]] += 1
            self._t0 = 0


GC_POLICY = GcPolicy()


@REGISTRY.collector
def _gc_lines() -> list[str]:
    """Collections and the wall time inside them while a runner ran —
    the plain totals the hook keeps."""
    return (["# HELP scheduler_gc_pause_seconds_total Wall time between "
             "the start and the stop of the cyclic garbage collector's "
             "collections, all generations; the interpreter lock is held "
             "throughout, so every thread waits",
             "# TYPE scheduler_gc_pause_seconds_total counter",
             f"scheduler_gc_pause_seconds_total {GC_POLICY.pause_ns * 1e-9}"]
            + series_lines(
                "scheduler_gc_collections_total", "counter",
                "Collections of the cyclic garbage collector by the oldest "
                "generation they walked (2 = a full collection)",
                "generation", dict(enumerate(GC_POLICY.collections))))
