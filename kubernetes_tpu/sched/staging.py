"""Zero-copy steady state: pre-sharded, double-buffered batch staging.

MULTICHIP_r06 pinned the sharded ConnectedMesh regression on ONE span:
``scheduler/stage_batch`` — the per-dispatch ``device_put`` of the pod
batch stack split on "pods" grew 381 -> 1641 ms under the mesh, because
``device_put`` re-lays-out every leaf against its NamedSharding on the
scheduling thread, inside the dispatch path. SNIPPETS [1]/[3] name the fix
exactly: ship inputs already pre-partitioned to match the program's
``in_axis_resources``.

Two pieces live here:

``StagingArena``
    A background "batch-stager" thread that uploads batch K+1's host stack
    into PRE-SHARDED device buffers while batch K's drain still runs:
    one batched sharded put, off the dispatch thread.
    Double-buffered: at most ``depth`` uploads in flight (the buffer being
    dispatched + the one uploading). At dispatch time
    ``Scheduler._stage_batch`` REDEEMS the ticket — a buffer swap, not a
    ``device_put``. Invalidation discipline mirrors the resident drain
    context: a mesh install/reshape (``SchedulerCache.set_mesh``) bumps the
    arena epoch and every in-flight ticket redeems to None — the caller
    falls back to the inline ``device_put`` path with bit-identical
    placements (the staged copy is a faithful snapshot of the submitted
    host stack, so a DECLINED swap never loses data, only the overlap).

``ResidentShadow``
    Host mirror of the resident cluster encoding's [N,R] allocatable /
    requested totals. The preemption wave used to ``device_get`` the two
    arrays from the resident context per wave — the one remaining host
    round-trip between a drain resolve and its preemption wave. The shadow
    is maintained from data the host already touches: winner folds are
    mirrored at resolve (lazily — request vectors are computed only when a
    wave actually needs the totals), churn patches apply their host-side
    ``req_delta``/``n_alloc``/``n_reset`` arrays. With it, the steady-state
    cycle's ONLY device->host transfer is the O(P) compact winners fetch.
"""

from __future__ import annotations

import logging
import queue as queue_mod
import threading
from typing import Any, Optional

import numpy as np

_LOG = logging.getLogger(__name__)

# bounded wait for an in-flight upload at redeem time: a stuck stager
# thread must degrade to the inline path, never hang the scheduling loop
REDEEM_WAIT_S = 30.0


class StageTicket:
    """One submitted upload: done Event + result slot + validity stamps."""

    __slots__ = ("done", "staged", "error", "epoch", "mesh", "nbytes")

    def __init__(self, epoch: int, mesh):
        self.done = threading.Event()
        self.staged = None
        self.error: Optional[BaseException] = None
        self.epoch = epoch
        self.mesh = mesh
        self.nbytes = 0


def _tree_nbytes(tree) -> int:
    import jax
    return int(sum(np.asarray(l).nbytes
                   for l in jax.tree_util.tree_leaves(tree)))


class StagingArena:
    """Double-buffered pre-sharded device staging for drain batch stacks."""

    def __init__(self, depth: int = 2):
        self.depth = max(1, int(depth))
        self._lock = threading.Lock()
        self._q: "queue_mod.Queue" = queue_mod.Queue()
        self._thread: Optional[threading.Thread] = None
        self._epoch = 0    # guarded by: self._lock
        self._inflight = 0  # guarded by: self._lock
        # health counters (ktpu status + bench legs report these) — shared
        # between the stager thread, the dispatch thread, and status readers
        self.swaps = 0        # guarded by: self._lock
        self.fallbacks = 0    # guarded by: self._lock
        self.submits = 0      # guarded by: self._lock
        self.bytes_staged = 0  # guarded by: self._lock

    # ---- lifecycle -------------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            t = threading.Thread(target=self._loop, daemon=True,
                                 name="batch-stager")
            self._thread = t
            t.start()

    def _loop(self) -> None:
        from kubernetes_tpu.parallel.mesh import stack_shardings
        while True:
            item = self._q.get()
            if item is None:  # poison pill from close()
                return
            ticket, pb_stack = item
            try:
                import jax
                staged = jax.device_put(
                    pb_stack, stack_shardings(ticket.mesh, pb_stack))
                jax.block_until_ready(staged)
                ticket.nbytes = _tree_nbytes(pb_stack)
                ticket.staged = staged
            except BaseException as e:  # noqa: BLE001 — redeem reports it
                ticket.error = e
                _LOG.warning("batch staging upload failed; dispatch will "
                             "stage inline", exc_info=True)
            finally:
                # the depth slot frees when the UPLOAD finishes, not at
                # redeem: a ticket a failed cycle never redeems must not
                # pin a slot forever (two leaks would silently disable
                # the arena for the process lifetime) — its staged
                # buffers are freed by GC when the ticket ref unwinds
                with self._lock:
                    self._inflight = max(0, self._inflight - 1)
                ticket.done.set()

    def close(self) -> None:
        t = self._thread
        if t is not None:
            self._q.put(None)
            self._thread = None
            t.join(timeout=2.0)  # drains the poison pill; uploads are short

    # ---- submit / redeem -------------------------------------------------

    def submit(self, pb_stack, mesh) -> Optional[StageTicket]:
        """Enqueue a pre-sharded upload of ``pb_stack``; returns a ticket to
        redeem at dispatch, or None when the double buffer is full (caller
        stages inline — never queues unboundedly behind a slow link)."""
        if mesh is None:
            return None
        with self._lock:
            if self._inflight >= self.depth:
                return None
            self._inflight += 1
            self.submits += 1
            ticket = StageTicket(self._epoch, mesh)
        self._ensure_thread()
        self._q.put((ticket, pb_stack))
        return ticket

    def redeem(self, ticket: Optional[StageTicket], mesh,
               timeout: float = REDEEM_WAIT_S):
        """The staged device buffers, or None (caller falls back to the
        legacy inline path). Declines when the arena was invalidated since
        submit (mesh install/reshape), the upload failed, the stager thread
        died, or the bounded wait expired."""
        if ticket is None:
            return None
        try:
            deadline = timeout
            while not ticket.done.wait(min(0.25, deadline)):
                deadline -= 0.25
                t = self._thread
                if deadline <= 0 or t is None or not t.is_alive():
                    _LOG.warning("batch-stager %s; staging inline",
                                 "died" if (t is None or not t.is_alive())
                                 else f"silent for {timeout:.0f}s")
                    with self._lock:
                        self.fallbacks += 1
                    return None
            with self._lock:
                stale = (ticket.epoch != self._epoch
                         or ticket.mesh is not mesh)
                if stale or ticket.error is not None \
                        or ticket.staged is None:
                    self.fallbacks += 1
                    return None
                self.swaps += 1
                self.bytes_staged += ticket.nbytes
                swaps = self.swaps
            from kubernetes_tpu.metrics.registry import (STAGE_BUFFER_REUSE,
                                                         STAGE_BYTES)
            STAGE_BYTES.inc({"path": "arena"}, by=ticket.nbytes)
            STAGE_BUFFER_REUSE.set(swaps)
            return ticket.staged
        finally:
            ticket.staged = None  # the arena never aliases redeemed buffers

    def invalidate(self) -> None:
        """Drop every in-flight ticket's validity (mesh install/reshape):
        redeems after this fall back to the inline path, which stages
        against the CURRENT mesh — a stale-layout swap can never happen."""
        with self._lock:
            self._epoch += 1

    def stats(self) -> dict:
        with self._lock:
            return {"submits": self.submits, "swaps": self.swaps,
                    "fallbacks": self.fallbacks,
                    "bytesStaged": self.bytes_staged,
                    "inflight": self._inflight}


class ResidentShadow:
    """Host mirror of the resident encoding's [N,R] totals (int64 numpy).

    Fed from three host-side sources that are exact mirrors of what the
    device program does to the resident arrays:

    - winner folds: ``drain_step`` adds each committed pod's request row
      into ``requested`` — the resolve loop appends (pod, node row) here
      and the vectors are computed LAZILY (``catch_up``) only when a
      preemption wave actually reads the totals;
    - churn patches: ``_apply_patch`` zeroes reset rows, adds
      ``req_delta``, and rewrites ``allocatable`` rows — ``apply_patch``
      replays the same numpy arrays the patch compile produced;
    - rebuilds: a fresh shadow is cut from the host encoding that staged
      the context.

    Any exception poisons the shadow (``ok`` False) and the wave falls
    back to the device readback — drift degrades to a fetch, never to a
    wrong answer. Parity with the device arrays is pinned by test.

    Thread contract: ``fold_winners`` runs on the RESOLVER thread while
    ``catch_up``/``apply_patch``/``arrays`` run on the scheduling thread —
    an unserialized ``pending`` swap could drop a resolve's winner folds
    on the floor (and a dropped fold is exactly the silent drift the
    poison discipline exists to prevent), so every access holds the lock.
    """

    def __init__(self, allocatable, requested):
        self._lock = threading.Lock()
        self.alloc = np.asarray(allocatable).astype(np.int64).copy()  # guarded by: self._lock
        self.req = np.asarray(requested).astype(np.int64).copy()  # guarded by: self._lock
        self.pending: list[tuple[Any, int]] = []  # guarded by: self._lock
        self.ok = True  # guarded by: self._lock

    def fold_winners(self, pairs: list) -> None:
        """Record winners mirrored at resolve: [(Pod, node_row)]."""
        with self._lock:
            self.pending.extend(pairs)

    def catch_up(self, vec_fn) -> None:
        """Fold pending winners' request vectors into ``requested``.
        ``vec_fn(pod) -> [R] int vector`` on the RESIDENT resource axis
        (the same ``_request_vector`` the encode and the device fold's
        batch rows use, so the mirror is bit-consistent)."""
        with self._lock:
            if not self.pending:
                return
            pending, self.pending = self.pending, []
            try:
                for pod, row in pending:
                    self.req[row] += np.asarray(vec_fn(pod), np.int64)
            except Exception:
                self.ok = False
                _LOG.exception("resident shadow catch-up failed; waves "
                               "fall back to the device readback")

    def apply_patch(self, patch: dict) -> None:
        """Mirror ``_apply_patch``'s requested/allocatable writes.

        ORDER CONTRACT: pending winner folds must be caught up FIRST (the
        scheduler calls ``catch_up`` before this) — on device the folds
        happened in earlier dispatches, strictly before this patch, so a
        patch that resets a row the device already folded a winner into
        must zero the winner's contribution too. Applying the patch with
        folds still pending would re-add that contribution to a reused
        row afterward. Un-caught-up pending entries poison the shadow
        rather than silently mis-mirroring."""
        with self._lock:
            if self.pending:
                self.ok = False
                _LOG.error("resident shadow patch applied with %d winner "
                           "folds pending; poisoning the shadow (waves "
                           "fall back to the device readback)",
                           len(self.pending))
                return
            try:
                rows = np.asarray(patch["node_row"])
                live = rows >= 0
                if live.any():
                    idx = rows[live]
                    self.alloc[idx] = np.asarray(patch["n_alloc"])[live]
                    reset = np.asarray(patch["n_reset"], bool) & live
                    if reset.any():
                        self.req[rows[reset]] = 0
                self.req += np.asarray(patch["req_delta"])
            except Exception:
                self.ok = False
                _LOG.exception("resident shadow patch mirror failed; "
                               "waves fall back to the device readback")

    def arrays(self):
        """(allocatable, requested) or None when the shadow is poisoned or
        still behind (pending winners not yet caught up). The returned
        arrays are the live mirrors (not copies): the wave encodes them
        on the scheduling thread, the same thread every mutator runs on —
        only ``fold_winners`` is foreign, and it never touches these."""
        with self._lock:
            if not self.ok or self.pending:
                return None
            return self.alloc, self.req
