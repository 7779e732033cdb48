"""Event recording — the EventRecorder/EventBroadcaster analog.

Reference: ``staging/src/k8s.io/client-go/tools/record/event.go``: components
record typed Events against objects ("FailedScheduling", "Scheduled",
"Killing", ...); identical events within a window aggregate into one Event
with a bumped ``count`` instead of flooding the store. Recording is
NON-BLOCKING, exactly like upstream (``recorder.Event`` pushes onto the
broadcaster's channel; watchers do the API writes on their own goroutine) —
the scheduler's binding cycle must never stall on an event POST. Consumers
read them via ``kubectl describe`` / ``kubectl get events``.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Optional

from kubernetes_tpu.metrics.registry import EVENTS_DROPPED
from kubernetes_tpu.utils.tracing import TRACER

EVENT_NORMAL, EVENT_WARNING = "Normal", "Warning"


class EventRecorder:
    """Write-behind recorder over a clientset: dedups (object, reason,
    message) within ``aggregate_window_s`` by bumping count, like the
    EventCorrelator. ``event()`` only enqueues; a single background sink
    thread performs the API writes (EventBroadcaster.StartRecordingToSink).
    Never lets event failures break the caller. ``flush()`` waits for the
    queue to drain (tests / shutdown)."""

    def __init__(self, client, component: str,
                 aggregate_window_s: float = 600.0, clock=None):
        from kubernetes_tpu.utils.clock import REAL_CLOCK
        self.client = client
        self.component = component
        self.aggregate_window_s = aggregate_window_s
        # event timestamps + the aggregation/prune windows read this clock,
        # so tests drive window expiry with a FakeClock instead of sleeping
        self.clock = clock or REAL_CLOCK
        self._lock = threading.Lock()
        # (ns, involved name, reason, message) -> (event name, count, ts)
        self._seen: dict[tuple, tuple[str, int, float]] = {}
        # per-recorder sequence keeps names unique within one millisecond
        self._seq = itertools.count()
        self._q: "queue.Queue[Optional[tuple]]" = queue.Queue(maxsize=4096)
        self._sink: Optional[threading.Thread] = None
        self._last_prune = 0.0

    def event(self, obj, type_: str, reason: str, message: str) -> None:
        if isinstance(obj, dict):
            md = obj.get("metadata") or {}
            kind = obj.get("kind", "")
        else:  # typed api objects
            md = {"name": obj.metadata.name,
                  "namespace": obj.metadata.namespace,
                  "uid": obj.metadata.uid}
            kind = type(obj).__name__
        ns = md.get("namespace") or "default"
        name = md.get("name", "")
        key = (ns, name, reason, message)
        now = self.clock.now()
        with self._lock:
            # prune entries too old to ever aggregate again (leak guard);
            # at most once per minute — event() runs on the scheduling loop,
            # and a full _seen scan per call would be O(events^2) per cycle
            if now - self._last_prune > 60.0:
                self._last_prune = now
                cutoff = now - self.aggregate_window_s
                for k in [k for k, v in self._seen.items() if v[2] < cutoff]:
                    del self._seen[k]
            prior = self._seen.get(key)
            if prior is None:
                ev_name = (f"{name}.{next(self._seq):x}"
                           f".{int(now * 1000) & 0xFFFFFF:x}")
                self._seen[key] = (ev_name, 1, now)
            else:
                ev_name = prior[0]
                self._seen[key] = (ev_name, prior[1] + 1, prior[2])
            if self._sink is None or not self._sink.is_alive():
                self._sink = threading.Thread(target=self._drain, daemon=True,
                                              name=f"events/{self.component}")
                self._sink.start()
            # enqueue under the lock: a same-key racer must not get its
            # aggregate (get+update) item into the queue ahead of the
            # original create item
            try:  # full queue = drop, like the broadcaster's channel overflow
                self._q.put_nowait(
                    (ns, name, kind, md.get("uid", ""), ev_name,
                     prior is not None, type_, reason, message, now))
            except queue.Full:
                # best-effort, but not silently so: a chaos run (or an
                # operator staring at a gap in `kubectl get events`) can
                # see exactly how many records the overflow ate
                EVENTS_DROPPED.inc({"reason": "queue_full"})

    def _drain(self) -> None:
        while True:
            item = self._q.get()
            # Batch: collect everything already queued behind this item and
            # flush creates in ONE bulk API call per namespace. Under a
            # binding storm ("Scheduled" per pod) the per-event POST chain
            # was ~25% of the whole connected path's host time.
            batch = [item]
            try:
                while len(batch) < 512:
                    batch.append(self._q.get_nowait())
            except queue.Empty:
                pass
            # one span a batch, on this sink's own thread: encoding and
            # writing what the scheduler recorded
            try:
                with TRACER.span("events/flush", events=len(batch)) as sp:
                    n_creates = self._write_batch(batch)
                    if sp is not None:
                        sp.attributes["creates"] = n_creates
            except Exception:
                EVENTS_DROPPED.inc({"reason": "sink_error"}, by=len(batch))
            finally:
                for _ in batch:
                    self._q.task_done()

    def _write_batch(self, batch: list) -> int:
        """One batch of queued events to the API: aggregates folded into
        a create of the same batch or written as an update, creates in ONE
        bulk call a namespace. -> the creates it sent."""
        creates: dict[str, list] = {}
        pending: dict[tuple, dict] = {}  # (ns, ev_name) -> queued create
        for it in batch:
            if it is None:
                continue
            (ns, name, kind, uid, ev_name, aggregate,
             type_, reason, message, now) = it
            if aggregate:
                prior = pending.get((ns, ev_name))
                if prior is not None:
                    # original create is in THIS batch: fold in place
                    prior["count"] += 1
                    prior["lastTimestamp"] = now
                    continue
                try:
                    self._write_aggregate(ns, ev_name, now)
                    continue
                except Exception:  # ktpu-lint: disable=KTL002 -- compaction probe lost a race; falling through writes a fresh event instead
                    pass  # fall through: write a fresh event
            pending[(ns, ev_name)] = obj = {
                "apiVersion": "v1", "kind": "Event",
                "metadata": {"name": ev_name, "namespace": ns},
                "involvedObject": {"kind": kind, "name": name,
                                   "namespace": ns, "uid": uid},
                "type": type_, "reason": reason, "message": message,
                "source": {"component": self.component},
                "count": 1, "firstTimestamp": now,
                "lastTimestamp": now}
            creates.setdefault(ns, []).append(obj)
        for ns, objs in creates.items():
            try:
                self.client.resource("events", ns).create_many(objs)
            except Exception:
                # best-effort: a failing client must neither raise
                # into the sink loop nor spin it — but every event
                # it eats is counted
                EVENTS_DROPPED.inc({"reason": "write_failed"},
                                   by=len(objs))
        return sum(len(objs) for objs in creates.values())

    def _write_aggregate(self, ns, ev_name, now) -> None:
        ev = self.client.resource("events", ns).get(ev_name)
        ev["count"] = ev.get("count", 1) + 1
        ev["lastTimestamp"] = now
        self.client.resource("events", ns).update(ev)

    def flush(self, timeout: float = 5.0) -> None:
        """Wait until every event recorded so far has been written."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self._q.unfinished_tasks == 0:
                return
            time.sleep(0.005)


class NullRecorder:
    """No-op recorder for components constructed without a client."""

    def event(self, obj, type_, reason, message) -> None:
        pass


def events_for(client, namespace: str, name: str,
               uid: Optional[str] = None) -> list[dict]:
    """Events whose involvedObject matches (describe's Events section).
    ``uid`` filters out a same-named PRIOR incarnation's events; events
    recorded without a uid still match (best effort)."""
    try:
        out = []
        listed = client.resource("events", namespace).list(
            field_selector=f"involvedObject.name={name}")
        for e in listed:
            if uid and (e.get("involvedObject") or {}).get("uid") \
                    and e["involvedObject"]["uid"] != uid:
                continue
            out.append(e)
    except Exception:  # ktpu-lint: disable=KTL002 -- best-effort event listing for kubectl describe; an unreachable apiserver shows no events
        return []
    out.sort(key=lambda e: e.get("lastTimestamp") or 0)
    return out
