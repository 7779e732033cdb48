"""Scheduling explainer — per-pod decision provenance off the hot path.

The batched schedulers (gang step, fused drain) reduce every per-(filter,
pod, node) verdict to one winner index; an unschedulable pod used to get
the generic "no node satisfied the pod's scheduling constraints this
cycle". This recovers what upstream's ``findNodesThatFitPod`` would have
said, WITHOUT adding a dispatch to the drain cycle:

- the scheduling thread hands each cycle's unschedulable pods (plus the
  typed cluster views the cycle judged against) to :class:`SchedulingExplainer`
  via ``submit`` — a capture + queue put, nothing more;
- a dedicated daemon thread (the ``audit/sentinel.py`` pattern) re-runs the
  STATIC filter stack in per-filter-output mode: one batched
  ``models/explain.explain_step`` dispatch over only the failed pods on a
  PRIVATE encoder (no cache-lock contention), or the numpy oracle when the
  device layer is degraded/broken;
- verdicts become (1) upstream-style ``FailedScheduling`` events
  ("0/N nodes are available: 3 Insufficient resources, ..."), (2) the
  ``scheduler-explanations`` ConfigMap ``ktpu why <pod>`` reads (published
  through a runner-supplied callback), and (3) the
  ``scheduler_unschedulable_reasons_total{filter}`` counter.

Out-of-tree tensor plugins and extender vetoes are outside the static
stack: pods from profiles that carry them still get the in-tree breakdown
(a superset explanation can overcount feasible nodes, never invent a
reject), and the explanation records the mode it was computed in.
"""

from __future__ import annotations

import logging
import queue as queue_mod
import threading
import time
from collections import OrderedDict
from typing import Callable, Optional

from kubernetes_tpu.metrics.registry import (
    EXPLAIN_CAPTURES,
    EXPLAIN_SAMPLES,
    LOOP_ERRORS,
    UNSCHEDULABLE_REASONS,
)
from kubernetes_tpu.utils.tracing import TRACER

_LOG = logging.getLogger(__name__)

# per-pod re-explanation throttle: a pod failing every backoff cycle gets
# one fresh verdict per window, not one per cycle (events aggregate the
# identical message anyway)
REEXPLAIN_INTERVAL_S = 2.0

# pods explained per batched dispatch (failed pods beyond this chunk go in
# further chunks); encode_pods pow2-buckets each chunk's width itself, so
# repeat cycles reuse the compiled explain program per bucket
MAX_EXPLAIN_BATCH = 256


class SchedulingExplainer:
    """Capture on the scheduling thread, judge + publish on a daemon
    thread. ``recorder_ref``/``publisher_ref`` are callables because the
    runner wires the real EventRecorder and ConfigMap publisher after the
    Scheduler (and this explainer) are constructed."""

    def __init__(self, cfg, recorder_ref: Callable[[], object],
                 max_backlog: int = 8, max_entries: int = 1024):
        self.cfg = cfg
        self._recorder_ref = recorder_ref
        # publisher(dict) -> None: writes the scheduler-explanations
        # ConfigMap (None = library embedder, explanations stay in-memory)
        self.publisher: Optional[Callable[[dict], None]] = None
        self._max_backlog = max_backlog
        self._max_entries = max_entries
        self._q: "queue_mod.Queue" = queue_mod.Queue()
        self._thread: Optional[threading.Thread] = None
        self._spawn_lock = threading.Lock()
        self._lock = threading.Lock()
        # pod key -> explanation dict (bounded, oldest evicted)
        self._explanations: "OrderedDict[str, dict]" = OrderedDict()
        self._last_explained: dict[str, float] = {}
        # private encoder: explanation encodes must never contend with the
        # drain cycle's encode lock (lazily built on the checker thread)
        self._encoder = None
        self.samples = 0
        self.pods_explained = 0
        self.errors = 0
        self.skipped = 0

    # ---- scheduling-thread half -----------------------------------------

    def submit(self, cache, profile, level: str, pods: list) -> bool:
        """Capture one cycle's unschedulable pods + the typed views the
        cycle judged against. Returns True when the explainer OWNS the
        FailedScheduling events for these pods (the caller then skips the
        generic event); False = backlog full / nothing to do, caller keeps
        the old behavior."""
        now = time.time()
        fresh = [p for p in pods
                 if now - self._last_explained.get(p.key, 0.0)
                 >= REEXPLAIN_INTERVAL_S]
        if not fresh:
            # every pod was explained moments ago; its event/ConfigMap
            # entry is still fresh — recording another identical generic
            # event would only be noise
            EXPLAIN_CAPTURES.inc({"result": "throttled"})
            return True
        if self._q.qsize() >= self._max_backlog:
            self.skipped += 1
            EXPLAIN_CAPTURES.inc({"result": "skipped"})
            return False
        for p in fresh:
            self._last_explained[p.key] = now
        if len(self._last_explained) > 4 * self._max_entries:
            cutoff = now - 10 * REEXPLAIN_INTERVAL_S
            self._last_explained = {
                k: t for k, t in self._last_explained.items() if t > cutoff}
        self.samples += 1
        self._ensure_thread()
        with TRACER.span("explain/capture", pods=len(fresh)) as sp:
            nodes = cache.list_nodes()
            bound = cache.bound_pods(include_assumed=True)
            if sp is not None:
                sp.attributes.update(nodes=len(nodes), bound=len(bound))
            self._q.put({"ts": now, "level": level,
                         "profile": profile.scheduler_name if profile else "",
                         "pods": list(fresh), "nodes": nodes, "bound": bound,
                         "ns_labels": cache.namespace_labels()})
        EXPLAIN_CAPTURES.inc({"result": "accepted"})
        return True

    def submit_direct(self, pod, message: str, filters: dict,
                      n_nodes: int, profile: str = "") -> bool:
        """A READY-MADE verdict from the scheduling thread — the carve
        path's "0/N origins can host a 2x2x4 slice" message, which no
        per-node judge can reconstruct (the free nodes individually pass;
        it's their composition into a contiguous box that failed).
        Recorded + published on the checker thread so ``ktpu why`` sees
        it; the EVENT stays with the caller (the scheduler already
        emitted the same message)."""
        now = time.time()  # ktpu-lint: disable=KTL003 -- same wall-clock re-explain throttle as submit() above (baselined); entries carry wall ts for ktpu why
        if now - self._last_explained.get(pod.key, 0.0) < REEXPLAIN_INTERVAL_S:
            EXPLAIN_CAPTURES.inc({"result": "throttled"})
            return True
        if self._q.qsize() >= self._max_backlog:
            self.skipped += 1
            EXPLAIN_CAPTURES.inc({"result": "skipped"})
            return False
        self._last_explained[pod.key] = now
        self.samples += 1
        self._ensure_thread()
        self._q.put({"direct": True, "key": pod.key,
                     "entry": {"message": message,
                               "filters": dict(filters),
                               "nodes": n_nodes, "feasibleNow": 0,
                               "unjudged": 0, "mode": "carve", "ts": now,
                               "profile": profile}})
        EXPLAIN_CAPTURES.inc({"result": "accepted"})
        return True

    # ---- results surface -------------------------------------------------

    def explanations(self) -> dict[str, dict]:
        with self._lock:
            return dict(self._explanations)

    def explain_of(self, key: str) -> Optional[dict]:
        with self._lock:
            return self._explanations.get(key)

    def stats(self) -> dict:
        return {"samples": self.samples,
                "podsExplained": self.pods_explained,
                "errors": self.errors, "skipped": self.skipped,
                "entries": len(self._explanations)}

    def drain(self, timeout: float = 10.0) -> None:
        """Block until every submitted capture's verdict landed (tests)."""
        deadline = time.time() + timeout
        while self._q.unfinished_tasks and time.time() < deadline:
            time.sleep(0.01)

    def close(self) -> None:
        t = self._thread
        if t is not None and t.is_alive():
            self._q.put(None)
            self._thread = None

    # ---- checker thread --------------------------------------------------

    def _ensure_thread(self) -> None:
        with self._spawn_lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="sched-explainer")
                self._thread.start()

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            try:
                if item.get("direct"):
                    self._record_direct(item)
                else:
                    self._explain(item)
            except Exception:
                # a broken explanation is counted and logged, never raised
                # into silence — and never into the scheduling loop either
                self.errors += 1
                LOOP_ERRORS.inc({"site": "explainer"})
                _LOG.exception("explanation failed (pods get no verdict "
                               "this cycle)")
            finally:
                self._q.task_done()

    def _profile(self, name: str):
        return self.cfg.profile_for(name)

    def _record_direct(self, item: dict) -> None:
        """Store + publish one submit_direct verdict (checker thread)."""
        entry = item["entry"]
        hist = entry.get("filters") or {}
        if hist:
            dominant = max(hist.items(), key=lambda kv: kv[1])[0]
            UNSCHEDULABLE_REASONS.inc({"filter": dominant})
        EXPLAIN_SAMPLES.inc({"mode": entry.get("mode", "carve")})
        self.pods_explained += 1
        with self._lock:
            self._explanations.pop(item["key"], None)
            self._explanations[item["key"]] = entry
            while len(self._explanations) > self._max_entries:
                self._explanations.popitem(last=False)
            snap = dict(self._explanations)
        if self.publisher is not None:
            try:
                self.publisher(snap)
            except Exception:
                LOOP_ERRORS.inc({"site": "explainer_publish"})
                _LOG.warning("explanations publish failed", exc_info=True)

    @staticmethod
    def _slice_shape(pod):
        """Label-based shape detection only: the capture carries no DRA
        catalog, and a claim-routed slice pod still explains usefully
        through the generic judges."""
        from kubernetes_tpu.topology.slicing import shape_of_labels
        return shape_of_labels(pod.metadata.labels)

    def _explain(self, item: dict) -> None:
        from kubernetes_tpu.models.explain import failed_scheduling_message
        pods, nodes = item["pods"], item["nodes"]
        profile = self._profile(item["profile"])
        views = (profile.apply_added_affinity(pods)
                 if profile is not None and profile.added_affinity else pods)
        mode = "tensor"
        with TRACER.span("explain/judge", pods=len(pods),
                         nodes=len(nodes)):
            try:
                if item["level"] == "oracle":
                    raise RuntimeError("device degraded; oracle explain")
                if any(self._slice_shape(v) is not None for v in views):
                    # slice-shaped pods: only the oracle judge carries the
                    # SliceCarve pseudo-filter (the carver's coverage
                    # plane) — the tensor stack has no such mask
                    raise RuntimeError("slice-shaped pod; oracle explain")
                per_pod = self._judge_tensor(item, views, profile)
            except Exception:
                _LOG.debug("tensor explain failed; falling back to the "
                           "oracle judge", exc_info=True)
                mode = "oracle"
                per_pod = self._judge_oracle(item, views)
        # per-pod: (histogram, feasible_now, unjudged). The tensor program
        # evaluates EVERY filter (disabled ones pass), so its first-fail
        # verdicts honor the profile natively; the oracle short-circuits,
        # so a rejection via a filter the profile disables hides any later
        # check — count those nodes as unjudged rather than blame a filter
        # the profile never ran (or worse, claim feasibility).
        per_pod = [(h, f, 0) for h, f in per_pod]
        if (mode == "oracle" and profile is not None
                and profile.enabled_filters is not None):
            # SliceCarve is not a disableable plugin — a profile's filter
            # allowlist must not demote its verdicts to "unjudged"
            enabled = set(profile.enabled_filters) | {"SliceCarve"}
            per_pod = [
                ({f: c for f, c in hist.items() if f in enabled}, feasible,
                 sum(c for f, c in hist.items() if f not in enabled))
                for hist, feasible, _u in per_pod]
        ts = item["ts"]
        recorder = self._recorder_ref()
        out: dict[str, dict] = {}
        for pod, (hist, feasible_now, unjudged) in zip(pods, per_pod):
            msg = failed_scheduling_message(len(nodes), hist, feasible_now,
                                            unjudged)
            if recorder is not None:
                recorder.event(pod, "Warning", "FailedScheduling", msg)
            if hist:
                dominant = max(hist.items(), key=lambda kv: kv[1])[0]
                UNSCHEDULABLE_REASONS.inc({"filter": dominant})
            EXPLAIN_SAMPLES.inc({"mode": mode})
            out[pod.key] = {"message": msg, "filters": hist,
                            "nodes": len(nodes),
                            "feasibleNow": feasible_now,
                            "unjudged": unjudged,
                            "mode": mode, "ts": ts,
                            "profile": item["profile"]}
        self.pods_explained += len(out)
        with self._lock:
            for k, v in out.items():
                self._explanations.pop(k, None)
                self._explanations[k] = v
            while len(self._explanations) > self._max_entries:
                self._explanations.popitem(last=False)
            snap = dict(self._explanations)
        if self.publisher is not None:
            with TRACER.span("explain/publish", entries=len(snap)):
                try:
                    self.publisher(snap)
                except Exception:
                    LOOP_ERRORS.inc({"site": "explainer_publish"})
                    _LOG.warning("explanations publish failed",
                                 exc_info=True)

    def _judge_tensor(self, item: dict, views: list, profile) -> list:
        """One batched per-filter-output dispatch over only the failed
        pods (chunked at the pow2 bucket) on the PRIVATE encoder.
        -> [(histogram, feasible_now)] per pod."""
        import jax
        import numpy as np
        from kubernetes_tpu.encode.snapshot import SnapshotEncoder
        from kubernetes_tpu.models.explain import (explain_step, first_fail,
                                                   reject_histogram)
        if self._encoder is None:
            self._encoder = SnapshotEncoder()
        enc = self._encoder
        enc.set_namespaces(item["ns_labels"])
        with TRACER.span("explain/encode", pods=len(views)):
            ct, meta = enc.encode_cluster(item["nodes"], item["bound"],
                                          pending_pods=views)
        enabled = (None if profile is None
                   or profile.enabled_filters is None
                   else tuple(sorted(profile.enabled_filters)))
        n_nodes = len(item["nodes"])
        out = []
        for i in range(0, len(views), MAX_EXPLAIN_BATCH):
            chunk = views[i:i + MAX_EXPLAIN_BATCH]
            pb = enc.encode_pods(chunk, meta, cache_rows=False)
            with TRACER.span("explain/dispatch", pods=len(chunk)):
                # ktpu-lint: disable=KTL005 -- background explainer thread, off the scheduling cycle by design (ExplainAB gates its overhead <= 5%)
                verdicts, valid = jax.device_get(
                    explain_step(ct, pb, topo_keys=meta.topo_keys,
                                 enabled=enabled))
            ff = first_fail(np.asarray(verdicts),
                            np.asarray(valid))[:len(chunk), :n_nodes]
            for row in ff:
                out.append((reject_histogram(row), int((row == -1).sum())))
        return out

    def _judge_oracle(self, item: dict, views: list) -> list:
        """Numpy-oracle fallback (degraded mode, device failure): the
        documented CPU path — same first-fail verdicts, serially."""
        from kubernetes_tpu.models.explain import REASON_TO_FILTER
        from kubernetes_tpu.sched.oracle import OracleScheduler
        orc = OracleScheduler(item["nodes"], item["bound"],
                              namespace_labels=item["ns_labels"])
        # arm the per-node SliceCarve gate (opt-in on the oracle): nodes
        # outside every carveable placement of a pod's requested shape
        # report SLICE_UNAVAILABLE instead of a misleading per-node pass
        orc.slice_explain = True
        out = []
        for pod in views:
            mask, reasons = orc.feasible(pod)
            hist: dict[str, int] = {}
            for reason in reasons.values():
                f = REASON_TO_FILTER.get(reason, reason)
                hist[f] = hist.get(f, 0) + 1
            out.append((hist, int(sum(mask))))
        return out

    # ---- on-demand score breakdown (scheduled pods) ----------------------

    def score_breakdown(self, nodes: list, bound: list, pod,
                        namespace_labels=None) -> Optional[dict]:
        """Why a SCHEDULED pod landed where it did: per-node combined
        scores from the oracle's score pipeline over the feasible set, with
        the top nodes listed. On-demand only (operator/library call) — the
        hot path never computes this."""
        import dataclasses
        from kubernetes_tpu.sched.oracle import OracleScheduler
        profile = self._profile(pod.spec.scheduler_name)
        orc = OracleScheduler(
            nodes, bound,
            weights=profile.weights() if profile is not None else None,
            namespace_labels=namespace_labels)
        view = pod
        if profile is not None and profile.added_affinity:
            view = profile.apply_added_affinity([pod])[0]
        # judge the pod as it looked AT SCHEDULING time: the nodeName its
        # binding wrote would pin the NodeName filter to one node
        view = dataclasses.replace(
            view, spec=dataclasses.replace(view.spec, node_name=""))
        mask, _reasons = orc.feasible(view)
        if not any(mask):
            return None
        scores = orc.score(view, mask)
        ranked = sorted(
            ((n.metadata.name, float(s))
             for n, s, ok in zip(nodes, scores, mask) if ok),
            key=lambda kv: -kv[1])
        return {"feasible": int(sum(mask)), "top": ranked[:5],
                "chosen": pod.spec.node_name or None}
