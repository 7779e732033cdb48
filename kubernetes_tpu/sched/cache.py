"""Scheduler cache — cluster state aggregation + assume/expire + snapshots.

Reference: ``pkg/scheduler/internal/cache/cache.go`` (``cacheImpl``:
AssumePod/FinishBinding/ForgetPod/UpdateSnapshot with generation counters).

The TPU twist: the expensive artifact is not per-node NodeInfo structs but the
encoded ClusterTensors. ``snapshot()`` re-encodes only when the cluster
generation moved (any node/pod add/update/remove or assume/forget), and the
persistent SnapshotEncoder keeps intern tables stable across snapshots so
re-encoding is allocation-churn only, not dictionary churn.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from kubernetes_tpu.api.types import Node, Pod, deep_copy
from kubernetes_tpu.encode.snapshot import ClusterTensors, SnapshotEncoder, SnapshotMeta


class SchedulerCache:
    def __init__(self, assume_ttl: float = 30.0):
        self._lock = threading.Lock()
        # Serializes ENCODER work (snapshot/encode_pods/overlay): the state
        # lock above stays cheap for informer handlers, while concurrent
        # snapshot() callers (scheduling loop + binder workers' volume path)
        # must not interleave delta pops/encodes on the shared encoder.
        self._encode_lock = threading.Lock()
        # informer threads that found the encode lock busy count that on
        # the encoder without it (a fleet runs one informer a tenant)
        self._lock_busy_count = threading.Lock()
        self._nodes: dict[str, Node] = {}  # guarded by: self._lock
        self._pods: dict[str, Pod] = {}  # guarded by: self._lock
        self._assumed: dict[str, tuple[Pod, float]] = {}  # guarded by: self._lock
        self._generation = 0  # guarded by: self._lock
        self._encoder = SnapshotEncoder()
        # churn headroom: free node rows absorb node ADDs as device patches,
        # spare label-value ids absorb the new values they intern (every
        # node interns its own name) — without these any node event would
        # overflow its bucket and force a rebuild
        import os
        self._encoder.node_headroom = int(
            os.environ.get("KTPU_NODE_HEADROOM", "64"))
        self._encoder.value_headroom = int(
            os.environ.get("KTPU_VALUE_HEADROOM", "256"))
        # fresh namespaces (e.g. churn traffic) must not widen the NSB
        # bucket mid-stream: that recompiles the drain inside the window
        self._encoder.ns_headroom = int(
            os.environ.get("KTPU_NS_HEADROOM", "16"))
        self._cached: Optional[tuple[int, ClusterTensors, SnapshotMeta]] = None  # guarded by: self._lock
        self.assume_ttl = assume_ttl
        self._volumes = None  # guarded by: self._lock (VolumeCatalog once any PVC/PV/SC appears)
        self._dra = None      # guarded by: self._lock (DraCatalog once any resource.k8s.io object appears)
        self._namespace_labels: dict[str, dict] = {}  # guarded by: self._lock
        # incremental-snapshot delta tracking (Cache.UpdateSnapshot analog):
        # pod churn accumulates here and patches the cached encoding in place;
        # anything structural (node add/remove, volumes) forces a full encode.
        self._delta_upserts: dict[str, Pod] = {}  # guarded by: self._lock
        self._delta_deletes: set[str] = set()  # guarded by: self._lock
        self._needs_full = True  # guarded by: self._lock
        # ---- ordered delta LOG for the device-resident drain context ----
        # Every encoding-relevant mutation appends (seq, op, payload); the
        # drain context replays entries since its last-consumed seq as
        # device-side patches (encode/patch.py) instead of dying on any
        # foreign change. Bounded; a consumer older than the window rebuilds.
        self._dlog: list[tuple] = []  # guarded by: self._lock
        self._dlog_start = 0   # guarded by: self._lock (seq of _dlog[0])
        self._dlog_seq = 0     # guarded by: self._lock (seq of the NEXT entry)
        self._snap_seq = 0     # guarded by: self._lock (log seq captured with the last snapshot)
        self._dlog_max = 100_000
        # encode-relevant node fingerprints: heartbeats that only touch
        # status/conditions must not invalidate the encoding at all
        self._node_fps: dict[str, tuple] = {}  # guarded by: self._lock
        # observability: full re-encodes performed by snapshot() (the
        # autoscaler's overlay path depends on snapshot freshness)
        self._full_encodes = 0  # guarded by: self._lock
        # active ("pods","nodes") scheduling mesh, or None (single-device).
        # The scheduler installs it (Scheduler.set_mesh); staging helpers
        # below then device_put encodings SHARDED so the drain programs run
        # under GSPMD instead of on one chip.
        self._mesh = None
        # pre-sharded double-buffered batch staging (sched/staging.py):
        # under a mesh, batch K+1 uploads on the background stager thread
        # while batch K runs and dispatch redeems a buffer swap; on one
        # device (no mesh) stage_drain_batch puts the batch inline.
        from kubernetes_tpu.sched.staging import StagingArena
        self._arena = StagingArena()

    # ---- device mesh -----------------------------------------------------

    def set_mesh(self, mesh) -> None:
        self._mesh = mesh
        # layout change: in-flight staged buffers carry the OLD shardings
        self._arena.invalidate()

    @property
    def mesh(self):
        return self._mesh

    def stage_submit(self, pb_stack):
        """Hand the final stacked drain batch to the staging arena: the
        background thread uploads it PRE-SHARDED while the scheduling
        thread finishes the cycle's host work (patch compile, sentinel
        capture) and the previous drain still executes. Returns a ticket
        for stage_redeem, or None (single-device / buffer full) — the
        dispatch then stages inline."""
        if self._mesh is None:
            return None
        return self._arena.submit(pb_stack, self._mesh)

    def stage_redeem(self, ticket):
        """Redeem a stage_submit ticket: the pre-staged device buffers, or
        None (invalidated/failed/timed out — caller stages inline)."""
        if ticket is None:
            return None
        return self._arena.redeem(ticket, self._mesh)

    def close_staging(self) -> None:
        self._arena.close()

    def stage_drain_batch(self, pb_stack):
        """INLINE staging of a stacked drain batch [B,P,...] — the
        fallback half of the staging pair (the steady state redeems a
        stage_submit ticket via stage_redeem instead; the scheduler's
        _stage_batch owns that flow and its span attribution). Under a
        mesh: one device_put split over "pods". Single-device: one
        EXPLICIT device_put so the drain dispatch performs zero implicit
        transfers (the transfer-guard invariant) at the same cost the
        jit's implicit staging paid."""
        import jax
        from kubernetes_tpu.metrics.registry import STAGE_BYTES
        from kubernetes_tpu.sched.staging import _tree_nbytes
        if self._mesh is None:
            staged = jax.device_put(pb_stack)
        else:
            from kubernetes_tpu.parallel.mesh import stack_shardings
            staged = jax.device_put(pb_stack,
                                    stack_shardings(self._mesh, pb_stack))
        STAGE_BYTES.inc({"path": "inline"}, by=_tree_nbytes(pb_stack))
        return staged

    def stage_patch(self, patch):
        """Explicitly stage a compiled churn patch's host arrays (~KB)
        before the dispatch that consumes them: replicated under a mesh,
        one device_put single-device — the fused drain then receives ONLY
        device-resident inputs (zero implicit transfers at dispatch)."""
        if patch is None:
            return None
        import jax
        if self._mesh is None:
            return jax.device_put(patch)
        from kubernetes_tpu.parallel.mesh import replicated
        rep = replicated(self._mesh)
        return jax.device_put(
            patch, jax.tree_util.tree_map(lambda _l: rep, patch))

    def staging_stats(self) -> dict:
        """Arena health for ktpu status / bench legs."""
        return dict(self._arena.stats(), enabled=self._mesh is not None)

    def request_vector(self, pod: Pod, resources: list) -> "np.ndarray":
        """One pod's scaled request vector on ``resources`` (the resident
        shadow's catch-up source) — same ``_request_vector`` the encode
        and patch paths use, under the encode lock (DRA catalog reads)."""
        with self._encode_lock:
            return self._encoder._request_vector(pod, resources)

    def with_encoder(self, fn):
        """Run ``fn(encoder)`` under the encode lock — the resident
        planners (encode/overlay.py) encode derived pod batches and build
        template planes against the LIVE encoder's intern tables, which
        must not interleave with snapshot/overlay work on other threads."""
        with self._encode_lock:
            return fn(self._encoder)

    # ---- delta log (drain-context patch feed) ----------------------------

    def _log_locked(self, op: str, payload):
        self._dlog.append((self._dlog_seq, op, payload))
        self._dlog_seq += 1
        if len(self._dlog) > self._dlog_max:
            drop = len(self._dlog) // 2
            del self._dlog[:drop]
            self._dlog_start += drop

    def deltas_since(self, seq: int):
        """Log entries with sequence >= ``seq`` in order, or None when the
        window no longer reaches back that far (consumer must rebuild)."""
        with self._lock:
            if seq < self._dlog_start:
                return None
            return self._dlog[seq - self._dlog_start:]

    def log_seq(self) -> int:
        with self._lock:
            return self._dlog_seq

    def last_snapshot_seq(self) -> int:
        """The log seq captured atomically with the last snapshot's state:
        a context built from that snapshot starts consuming here."""
        with self._lock:
            return self._snap_seq

    # ---- volume catalog (PVC/PV/StorageClass informers feed this) --------

    def update_volume_object(self, kind: str, obj: dict, deleted: bool = False):
        """Track PVC/PV/StorageClass state for the VolumeBinding tensors."""
        from kubernetes_tpu.sched.volumebinding import VolumeCatalog
        with self._lock:
            if self._volumes is None:
                self._volumes = VolumeCatalog()
            md = obj.get("metadata") or {}
            if kind == "PersistentVolumeClaim":
                key = (md.get("namespace", "default"), md.get("name", ""))
                space = self._volumes.pvcs
            elif kind == "PersistentVolume":
                key = md.get("name", "")
                space = self._volumes.pvs
            else:
                key = md.get("name", "")
                space = self._volumes.storage_classes
            if deleted:
                space.pop(key, None)
            else:
                space[key] = obj
            self._encoder.set_volumes(self._volumes)
            self._generation += 1
            self._needs_full = True
            self._log_locked("full", None)

    @property
    def volume_catalog(self):
        with self._lock:
            return self._volumes

    # ---- DRA objects (resource.k8s.io informers feed this) ---------------

    def update_dra_object(self, kind: str, obj: dict, deleted: bool = False):
        """Track ResourceClaim/DeviceClass/ResourceSlice state; device
        classes become dra:<class> resources in the next encoding.

        Claim STATUS churn (allocation/reservedFor — which the scheduler
        itself writes on every bind of a claimed pod) must not invalidate
        the cluster encoding: pod batches read the live catalog at encode
        time, and the cluster tensors only depend on claim SPECS (bound
        pods' demands), slices, and the class set."""
        from kubernetes_tpu.sched.dra import DraCatalog
        with self._lock:
            if self._dra is None:
                self._dra = DraCatalog()
            md = obj.get("metadata") or {}
            if kind == "ResourceClaim":
                key = (md.get("namespace", "default"), md.get("name", ""))
                space = self._dra.claims
            elif kind == "DeviceClass":
                key = md.get("name", "")
                space = self._dra.classes
            elif kind == "ResourceSlice":
                key = md.get("name", "")
                space = self._dra.slices
            else:
                return
            old = space.get(key)
            if deleted:
                if space.pop(key, None) is None:
                    return
            else:
                space[key] = obj
            if (kind == "ResourceClaim" and old is not None and not deleted
                    and DraCatalog.claim_demands(old)
                    == DraCatalog.claim_demands(obj)):
                # status-only change: encoding-neutral. Checked BEFORE
                # set_dra — the scheduler writes claim status on every bind
                # of a claimed pod, and letting that bump the encoder's pod
                # epoch would invalidate the whole precompile cache per
                # bind (the catalog object is shared and already mutated
                # in place above, so skipping set_dra loses nothing).
                return
            self._encoder.set_dra(self._dra)
            self._generation += 1
            self._needs_full = True
            self._log_locked("full", None)

    @property
    def dra_catalog(self):
        with self._lock:
            return self._dra

    # ---- namespace labels (Namespace informer feeds this) ----------------

    def update_namespace(self, obj: dict, deleted: bool = False):
        """Track namespace labels so affinity terms' namespaceSelector
        resolves at encode time (GetNamespaceLabelsSnapshot analog)."""
        from kubernetes_tpu.encode.snapshot import TENANT_LABEL
        with self._lock:
            md = obj.get("metadata") or {}
            name = md.get("name", "")
            if deleted:
                old = self._namespace_labels.pop(name, None)
                if old is None:
                    return
                tenants = {(old or {}).get(TENANT_LABEL)}
            else:
                new = dict(md.get("labels") or {})
                old = self._namespace_labels.get(name)
                if old == new:
                    return  # label-neutral churn: keep the encoding valid
                self._namespace_labels[name] = new
                # per-tenant catalog-epoch discipline: nsSelector resolution
                # is tenant-scoped, so only the touched tenants' precompiled
                # pod records go stale (old AND new tenant when relabelled)
                tenants = {new.get(TENANT_LABEL),
                           (old or {}).get(TENANT_LABEL)}
            self._encoder.set_namespaces(self._namespace_labels,
                                         changed_tenants=tenants)
            self._generation += 1
            # Pod batches always read the fresh snapshot at encode time; the
            # CLUSTER encoding only goes stale if an existing pod's anti term
            # actually resolved a namespaceSelector against the old labels.
            if self._encoder.cluster_depends_on_namespace_labels:
                self._needs_full = True
                self._log_locked("full", None)

    # ---- node events -----------------------------------------------------

    @staticmethod
    def _node_fp(node: Node) -> tuple:
        """Fingerprint of the encode-relevant node fields; status-only churn
        (heartbeat conditions) leaves it unchanged."""
        return (
            tuple(sorted(node.status.allocatable.items())),
            tuple(sorted(node.metadata.labels.items())),
            tuple((t.key, t.value, t.effect) for t in node.spec.taints),
            node.spec.unschedulable,
            tuple((tuple(i.names[:1]), i.size_bytes)
                  for i in node.status.images),
        )

    def add_node(self, node: Node):
        with self._lock:
            fp = self._node_fp(node)
            prev = self._node_fps.get(node.metadata.name)
            self._nodes[node.metadata.name] = node
            if prev == fp:
                return  # heartbeat-only update: encoding unaffected
            self._node_fps[node.metadata.name] = fp
            self._generation += 1
            self._needs_full = True
            self._log_locked("node", node)

    def update_node(self, node: Node):
        self.add_node(node)

    def remove_node(self, name: str):
        with self._lock:
            if self._nodes.pop(name, None) is not None:
                self._node_fps.pop(name, None)
                self._generation += 1
                self._needs_full = True
                self._log_locked("nodedel", name)

    # ---- pod events ------------------------------------------------------

    def add_pod(self, pod: Pod):
        """Bound pod observed (informer). Confirms an assume if present.

        Confirmation of an assume on the SAME node is encoding-neutral: the
        assume already patched this pod into the tensors, and nothing the
        encoder reads (node, namespace, labels, requests) changes between
        the assumed copy and the watch-confirmed object — so the cached
        encoding stays valid and the confirm costs a dict move, not a
        tensor patch. Under a binding storm this removes one incremental
        patch per bound pod (the whole fleet confirms within seconds).

        STATUS-only churn on an already-bound pod is encoding-neutral too
        (the pod twin of the node-fingerprint check): kubelets rewrite
        ``status`` on every sync, and each such MODIFIED used to append a
        ``pod`` delta — at fleet scale that made nearly every drain cycle
        compile a patch over hundreds of unchanged pods (and cross patch
        write-buckets, recompiling the fold program mid-window; the bulk
        of MULTICHIP_r06's 1.4-1.9s ctx_patch_apply was exactly this).
        The encoder reads labels + spec only, so equality there keeps the
        encoding valid; the stored object still refreshes."""
        with self._lock:
            if not pod.spec.node_name:
                return
            prior = self._assumed.pop(pod.key, None)
            old = self._pods.get(pod.key)
            self._pods[pod.key] = pod
            if prior is not None:
                ap = prior[0]
                if (ap.spec.node_name == pod.spec.node_name
                        and ap.metadata.labels == pod.metadata.labels
                        and pod.key not in self._delta_deletes):
                    return  # pure confirmation: encoding unaffected
            elif (old is not None and pod.key not in self._delta_deletes
                    and old.metadata.labels == pod.metadata.labels
                    and old.spec.to_dict() == pod.spec.to_dict()):
                return  # status-only update: encoding unaffected
            self._generation += 1
            self._delta_upserts[pod.key] = pod
            self._delta_deletes.discard(pod.key)
            self._log_locked("pod", pod)
            # bound: it will never pass through encode_pods again
            self._encoder.pod_cache_discard(pod.key)

    def update_pod(self, pod: Pod):
        self.add_pod(pod)

    def confirm(self, pod_key: str, node_name: str, labels: dict,
                spec: Optional[dict] = None) -> bool:
        """Fast-path bind confirmation: promote the assumed copy to bound
        when the watch event matches it — the dict-level twin of add_pod's
        pure-confirmation branch. Lets the informer skip a full
        Pod.from_dict per binding event: under a gang bind storm every bound
        pod produces exactly one MODIFIED whose only news is the node the
        cache already assumed.

        ``spec``: the event's raw spec dict; when given, it must equal the
        assumed copy's spec (nodeName aside) or the promotion is refused —
        a spec PUT racing the bind would otherwise install the stale assumed
        copy as bound with no later event to heal it (add_pod stores the
        fresh watch object instead, so the fallback self-heals). Returns
        False when there is nothing to confirm (caller falls back)."""
        with self._lock:
            prior = self._assumed.get(pod_key)
            if prior is None or pod_key in self._delta_deletes:
                return False
            ap = prior[0]
            if ap.spec.node_name != node_name or ap.metadata.labels != labels:
                return False
            if spec is not None:
                mine = ap.spec.to_dict()
                mine.pop("nodeName", None)
                theirs = {k: v for k, v in spec.items() if k != "nodeName"}
                if mine != theirs:
                    return False
            del self._assumed[pod_key]
            self._pods[pod_key] = ap
            self._encoder.pod_cache_discard(pod_key)
            return True

    def is_bound(self, pod_key: str) -> bool:
        """True if the pod is recorded as bound (confirmed via watch)."""
        with self._lock:
            return pod_key in self._pods

    def is_assumed_or_bound(self, pod_key: str) -> bool:
        """True if the pod holds capacity (assumed OR confirmed) — the
        mid-cycle rescue path must not requeue a pod whose placement this
        very cycle already committed."""
        with self._lock:
            return pod_key in self._pods or pod_key in self._assumed

    def remove_pod(self, pod_key: str):
        with self._lock:
            existed = self._pods.pop(pod_key, None) or self._assumed.pop(pod_key, None)
            self._encoder.pod_cache_discard(pod_key)
            if existed:
                self._generation += 1
                self._delta_upserts.pop(pod_key, None)
                self._delta_deletes.add(pod_key)
                self._log_locked("poddel", pod_key)

    # ---- optimistic binding ---------------------------------------------

    def assume(self, pod: Pod, node_name: str):
        """Optimistically treat the pod as bound NOW (AssumePod); the binding
        confirms via add_pod or expires after assume_ttl. Stores a copy — the
        caller's pod object stays unbound so a failed binding can requeue it
        cleanly (the reference deep-copies into the cache for the same
        reason). The copy is two-level (new Pod + new spec, shared leaves):
        nothing mutates pod subtrees in place — informers build a fresh Pod
        per event — so a structural deep copy (~30us/pod, the old path) only
        burned time on the hot batch loop."""
        import dataclasses
        with self._lock:
            p = dataclasses.replace(
                pod, spec=dataclasses.replace(pod.spec, node_name=node_name))
            self._assumed[p.key] = (p, time.time() + self.assume_ttl)
            self._generation += 1
            self._delta_upserts[p.key] = p
            self._delta_deletes.discard(p.key)
            self._log_locked("assume", (p.key, node_name, p))
            # placed: the record is dead unless the binding fails, and a
            # rare bind-failure retry recompiling one pod beats keeping
            # every placed pod's record alive (forget() keeps nothing)
            self._encoder.pod_cache_discard(p.key)

    def assume_many(self, pairs: list) -> None:
        """assume() for a whole drain's winners in ONE lock pass — the gang
        step commits thousands of placements per resolve, and a lock
        round-trip per pod was measurable against the connected window.
        ``pairs``: [(Pod, node_name)]. Advances the generation by exactly
        len(pairs), which the drain context's resolve-side currency check
        (scheduler._resolve_pending) counts on."""
        import dataclasses
        with self._lock:
            deadline = time.time() + self.assume_ttl
            for pod, node_name in pairs:
                p = dataclasses.replace(
                    pod, spec=dataclasses.replace(pod.spec,
                                                  node_name=node_name))
                self._assumed[p.key] = (p, deadline)
                self._delta_upserts[p.key] = p
                self._delta_deletes.discard(p.key)
                self._log_locked("assume", (p.key, node_name, p))
                self._encoder.pod_cache_discard(p.key)
            self._generation += len(pairs)

    def finish_binding(self, pod_key: str):
        """Binding RPC done; keep assumed until the watch confirms (TTL holds)."""

    def forget(self, pod_key: str):
        """Binding failed: drop the assumption (ForgetPod)."""
        with self._lock:
            if self._assumed.pop(pod_key, None):
                self._generation += 1
                self._delta_upserts.pop(pod_key, None)
                self._delta_deletes.add(pod_key)
                self._log_locked("poddel", pod_key)

    def _expire_assumed_locked(self):
        now = time.time()
        expired = [k for k, (_, dl) in self._assumed.items() if dl < now]
        for k in expired:
            del self._assumed[k]
            self._delta_upserts.pop(k, None)
            self._delta_deletes.add(k)
            self._log_locked("poddel", k)
        if expired:
            self._generation += 1

    # ---- snapshot --------------------------------------------------------

    def snapshot(self, pending_pods: Optional[list[Pod]] = None,
                 slot_headroom: int = 0):
        """-> (nodes list, ClusterTensors, SnapshotMeta).

        Three paths, mirroring ``Cache.UpdateSnapshot``:
          clean     — nothing changed: return the cached encoding.
          pod delta — only pod binds/unbinds since the last snapshot: patch
                      the cached tensors in place (apply_pod_deltas).
          full      — structural change (node add/remove/relabel, volumes,
                      bucket overflow, new resource kind): re-encode.

        ``pending_pods`` widen the resource axis; passing a batch with a new
        extended resource forces the full path (rare).

        Locking: state is COLLECTED under the state lock, then the encode
        runs under the ENCODE lock only — the state lock is shared with
        every informer handler, and holding it across a multi-hundred-ms
        encode made each watch event (add_pod) stall behind the batch cycle
        (lock-convoy, not useful work). Deltas that arrive mid-encode simply
        stay queued for the next snapshot; if a structural change lands
        mid-encode, _needs_full survives (we only clear flags captured
        before the encode began). The encode lock serializes concurrent
        snapshot() callers (scheduling loop + binder workers) so delta pops
        can't interleave on the shared encoder.
        """
        with self._encode_lock:
            return self._snapshot_serialized(pending_pods, slot_headroom)

    def _export_gauges_locked(self):
        from kubernetes_tpu.metrics.registry import (
            CACHE_FULL_ENCODES,
            CACHE_GENERATION,
            ENCODE_POD_CACHE_HITS,
            ENCODE_POD_CACHE_MISSES,
            ENCODE_POD_ROWS_FILLED,
            ENCODE_POD_ROWS_STACKED,
        )
        CACHE_GENERATION.set(self._generation)
        CACHE_FULL_ENCODES.set(self._full_encodes)
        ENCODE_POD_CACHE_HITS.set(self._encoder.pod_cache_hits)
        ENCODE_POD_CACHE_MISSES.set(self._encoder.pod_cache_misses)
        ENCODE_POD_ROWS_STACKED.set(self._encoder.pod_rows_stacked)
        ENCODE_POD_ROWS_FILLED.set(self._encoder.pod_rows_filled)

    def _snapshot_serialized(self, pending_pods, slot_headroom):
        with self._lock:
            self._expire_assumed_locked()
            self._export_gauges_locked()
            self._snap_seq = self._dlog_seq
            nodes = list(self._nodes.values())
            gen = self._generation
            cached = self._cached
            needs_full = self._needs_full
            upserts = deletes = None
            bound = None
            if cached is not None and not needs_full:
                _, ct0, meta0 = cached
                known = set(meta0.resources)
                widen = any(r not in known for p in (pending_pods or [])
                            for r in p.resource_requests())
                if not widen:
                    if not self._delta_upserts and not self._delta_deletes:
                        return nodes, ct0, meta0
                    upserts = list(self._delta_upserts.values())
                    deletes = list(self._delta_deletes)
                    self._delta_upserts.clear()
                    self._delta_deletes.clear()
            if upserts is None:
                bound = (list(self._pods.values())
                         + [p for p, _ in self._assumed.values()])
                self._delta_upserts.clear()
                self._delta_deletes.clear()

        # ---- encode outside the lock (scheduler thread only) -------------
        if upserts is not None:
            _, ct0, meta0 = cached
            patched = self._encoder.apply_pod_deltas(ct0, meta0, upserts,
                                                     deletes)
            if patched is not None:
                with self._lock:
                    self._cached = (gen, patched, meta0)
                return nodes, patched, meta0
            # patch didn't fit the buckets: fall through to a full encode,
            # folding the popped deltas back into the bound view
            with self._lock:
                bound = (list(self._pods.values())
                         + [p for p, _ in self._assumed.values()])
                self._delta_upserts.clear()
                self._delta_deletes.clear()
        ct, meta = self._encoder.encode_cluster(nodes, bound,
                                                pending_pods=pending_pods,
                                                slot_headroom=slot_headroom)
        with self._lock:
            self._cached = (gen, ct, meta)
            if self._generation == gen:
                self._needs_full = False
            self._full_encodes += 1
            self._export_gauges_locked()
        return nodes, ct, meta

    def patch_state_fork(self):
        """CtxPatchState forked from the encoder's post-encode bookkeeping
        (encode/patch.py) — the drain context's private slot/row maps."""
        from kubernetes_tpu.encode.patch import fork_patch_state
        with self._encode_lock:
            return fork_patch_state(self._encoder._patch)

    def compile_ctx_patch(self, meta, cs, entries, nom_target: dict,
                          nom_bucket: int, fold_floor: int = 0):
        """compile_patch under the encode lock (interning is shared with
        snapshot/encode_pods and must not interleave)."""
        from kubernetes_tpu.encode.patch import compile_patch
        with self._encode_lock:
            return compile_patch(self._encoder, meta, cs, entries,
                                 nom_target, nom_bucket,
                                 fold_floor=fold_floor)

    def encode_pods(self, pods: list[Pod], meta: SnapshotMeta,
                    min_p: int = 1, cache_rows: bool = True):
        with self._encode_lock:
            return self._encoder.encode_pods(pods, meta, min_p=min_p,
                                             cache_rows=cache_rows)

    def precompile_pod(self, pod: Pod) -> None:
        """Informer-event-time half of the incremental encode: give the pod
        its encode record and its row pack NOW (watch thread) so the
        drain's encode_pods later only assembles. Work done here is moved
        between two threads of one interpreter, not hidden: the loop waits
        for the GIL while this runs, so what it costs is paid by the window
        all the same (PERF.md section 5). Hence a pack holds only the
        constraint groups its pod populates, and record and pack are built
        once a pod TEMPLATE (the encoder's template store): what still runs
        once a pod is ``Pod.from_dict``, the template key, two shallow
        copies, the pod's own ``requests`` vector and ``queue.add``.
        NON-BLOCKING on the encode lock — if the scheduling loop is
        mid-encode, skipping is strictly better than convoying the watch
        thread behind a multi-hundred-ms encode: the pod is counted a
        ``bypass`` of ``scheduler_encode_pod_template_total`` and meets its
        template on the hot path instead (``encode_pods``' miss branch)."""
        if not self._encode_lock.acquire(blocking=False):
            with self._lock_busy_count:
                self._encoder.pod_template_lock_busy += 1
            return
        try:
            self._encoder.precompile_pod(pod)
        except Exception:  # ktpu-lint: disable=KTL002 -- best-effort warm-up; encode_pods recompiles this pod authoritatively on the hot path, so a precompile failure costs latency, never correctness
            pass
        finally:
            self._encode_lock.release()

    def encode_cache_stats(self) -> dict[str, int]:
        """Hit/miss counters of the pod compile cache plus the row-pack
        assembly split (benchmarks report these: a healthy connected run
        shows hits >> misses and rows_stacked >> rows_filled). A stacked
        row's pack was on the pod's record when the drain popped it, made
        on the informer's thread rather than the loop's — moved, not saved.
        What was saved is the template store's account, pods that took an
        earlier pod's compiled record and pack against pods that built one
        (``scheduler_encode_pod_template_total``); how much a built pack
        holds is the encoder's ``row_groups_built`` / ``row_groups_default``
        pair (``scheduler_encode_row_groups_total``)."""
        return {"hits": self._encoder.pod_cache_hits,
                "misses": self._encoder.pod_cache_misses,
                "rows_stacked": self._encoder.pod_rows_stacked,
                "rows_filled": self._encoder.pod_rows_filled}

    def overlay_nominated(self, ct, meta, entries, min_m: int = 0):
        """ct with nominated-pod reservations applied (encoder.with_nominated);
        entries: [(node_name, priority, Pod)]."""
        with self._encode_lock:
            return self._encoder.with_nominated(ct, meta, entries,
                                                min_m=min_m)

    def get_node(self, name: str) -> Optional[Node]:
        """Cheap single-node lookup (binder-side volume labels); avoids a
        full snapshot from non-scheduling threads."""
        with self._lock:
            return self._nodes.get(name)

    def list_nodes(self) -> list[Node]:
        """Plain node list WITHOUT an encode pass — the oracle fallback
        path reads typed objects only, so a broken device layer never
        stands between it and the cluster state."""
        with self._lock:
            return list(self._nodes.values())

    def namespace_labels(self) -> dict[str, dict]:
        """Namespace -> labels view (the oracle's namespaceSelector
        resolution source)."""
        with self._lock:
            return dict(self._namespace_labels)

    def delta_info(self) -> tuple[int, set, bool, bool]:
        """-> (generation, pending upsert keys, any deletes pending,
        needs_full). The device-resident drain uses this to prove its HBM
        replica of the encoding is still exactly one fold behind the cache
        (every pending delta is an assume it already folded device-side)."""
        with self._lock:
            return (self._generation, set(self._delta_upserts),
                    bool(self._delta_deletes), self._needs_full)

    def bound_pods(self, include_assumed: bool = True) -> list[Pod]:
        with self._lock:
            out = list(self._pods.values())
            if include_assumed:
                out += [p for p, _ in self._assumed.values()]
            return out

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {"nodes": len(self._nodes), "pods": len(self._pods),
                    "assumed": len(self._assumed),
                    "generation": self._generation,
                    "full_encodes": self._full_encodes}

    def audit_view(self) -> dict:
        """One-lock-pass consistent view for the invariant auditor:
        confirmed-bound and assumed placements (key -> node), the node-name
        set, and the generation. Plain values only — the auditor runs on
        its own thread and must never hold references that alias the
        cache's mutable state."""
        with self._lock:
            return {
                "bound": {k: p.spec.node_name
                          for k, p in self._pods.items()},
                "assumed": {k: p.spec.node_name
                            for k, (p, _dl) in self._assumed.items()},
                "nodes": set(self._nodes),
                "generation": self._generation,
            }
