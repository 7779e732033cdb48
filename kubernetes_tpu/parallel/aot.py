"""AOT lowering/serialization helpers, the honest compile meter, and the
one place that decides where the persistent compile cache lives.

Three small pieces the durable executable cache (sched/aotcache.py), the
process entry points and the benchmarks build on:

1. ``lowering_fingerprint`` — one string that changes iff a cached
   compiled program could be invalid for THIS process: jax/jaxlib
   versions, the backend platform and device population, the XLA flag
   environment, plus any caller-declared config knobs that change
   lowering. The AOT cache invalidates wholesale on mismatch.

2. ``CompileMeter`` / ``compile_meter()`` — the cache-aware successor to
   the FleetChurn bench's backend-compile counter. On this toolchain the
   ``backend_compile`` *duration* event fires even when the compiled
   executable was LOADED from the persistent cache (pxla wraps
   ``compile_or_get_cached`` in the timing scope), so counting duration
   events alone would read a warm-from-disk boot as a compile storm.
   Genuine XLA work is ``backend_compile`` events MINUS persistent-cache
   hit events; the meter tracks all three so a "ZERO compiles" gate can
   be asserted honestly with the cache on, and degrades to the old
   meaning (hits are simply 0) with it off.

3. ``place_compile_cache`` — where jax's persistent compilation cache
   goes. ``JAX_COMPILATION_CACHE_DIR`` (placed from OUTSIDE the program)
   always wins and is never re-pointed or cleaned by this code; without
   it, process entry points (chip_smoke.py, the benchmarks' mains,
   ``ktpu-up``, the chaos scheduler child) use ONE fixed,
   git-ignored directory inside the checkout — the path is part of the
   cache key, so a directory that moves never hits. Library construction
   of a ``SchedulerRunner`` places nothing (tier-1 stays cache-free).

``serialize_compiled``/``deserialize_compiled`` wrap
``jax.experimental.serialize_executable`` for explicit per-executable
AOT round-trips (the parity tests pin that a deserialized executable
answers bit-identically); the cache itself rides XLA's own entry format
so LIVE jit dispatches — not just pre-lowered handles — load from disk.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Optional

_METER_LOCK = threading.Lock()
_METER: Optional["CompileMeter"] = None

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# parallel/ -> kubernetes_tpu/ -> the checkout. Fixed on purpose: never a
# temp name, a pid or a time (listed in .gitignore).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def external_cache_dir() -> Optional[str]:
    """The cache directory placed from outside the program, or None."""
    return os.environ.get(CACHE_DIR_ENV) or None


def point_jax_cache_at(path: Optional[str]) -> None:
    """Point jax's persistent compilation cache at ``path`` (None
    detaches it). jax decides ONCE per process whether a cache is in use
    and keeps the directory handle open, so a changed directory only
    takes effect after ``reset_cache``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    if jax.config.jax_compilation_cache_dir == path:
        return
    jax.config.update("jax_compilation_cache_dir", path)
    cc.reset_cache()


def persist_every_program() -> None:
    """Drop jax's persist thresholds (entries under 1 s of compile time
    are skipped by default): a zero-compile gate counts the tiny staging
    jits too, so every warmed program must persist."""
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def place_compile_cache() -> str:
    """Process entry points call this once, before their first jit.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads it and no
    directory is set here; otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE_DIR``. Returns the directory in effect."""
    path = external_cache_dir()
    if path is None:
        path = DEFAULT_COMPILE_CACHE_DIR
        point_jax_cache_at(path)
    persist_every_program()
    return path


def lowering_fingerprint(knobs: Optional[dict] = None) -> str:
    """Hex digest of everything that must match for a cached executable
    to be trusted by this process. ``knobs`` is the caller's dict of
    lowering-relevant config (mesh shape, donation mode, ...); it must be
    JSON-serializable with a stable ordering."""
    import jax
    try:
        backend = jax.devices()[0]
        device = {"platform": backend.platform,
                  "kind": backend.device_kind,
                  "count": jax.device_count()}
    except Exception:  # ktpu-lint: disable=KTL002 -- no backend yet is a legitimate state; the fingerprint records the absence
        device = {"platform": None, "kind": None, "count": 0}
    import jaxlib.version
    doc = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.version.__version__,
        "device": device,
        "xlaFlags": os.environ.get("XLA_FLAGS", ""),
        "knobs": knobs or {},
    }
    return hashlib.sha256(
        json.dumps(doc, sort_keys=True, default=str).encode()).hexdigest()


def serialize_compiled(compiled) -> bytes:
    """One compiled (``jit(...).lower(...).compile()``) executable ->
    portable bytes. The in/out tree definitions ride along, pickled by
    jax's own helper."""
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree = se.serialize(compiled)
    import pickle
    return pickle.dumps((payload, in_tree, out_tree))


def deserialize_compiled(blob: bytes):
    """Inverse of :func:`serialize_compiled` -> a loaded executable whose
    ``call`` matches the original's."""
    from jax.experimental import serialize_executable as se
    import pickle
    payload, in_tree, out_tree = pickle.loads(blob)
    return se.deserialize_and_load(payload, in_tree, out_tree)


class CompileMeter:
    """Process-wide compile/cache event counts from ``jax.monitoring``.

    Listeners cannot be unregistered on this toolchain, so the meter is a
    register-once singleton (``compile_meter()``); callers take
    ``snapshot()``s and diff them to attribute counts to one window —
    the same discipline the benchmarks already use for metric counters."""

    def __init__(self):
        self._lock = threading.Lock()
        self.backend_compiles = 0   # duration events: compile OR cache load
        self.cache_hits = 0         # persistent-cache loads
        self.cache_misses = 0       # genuine compiles (cache enabled)
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, _dur, **_kw) -> None:
        if "backend_compile" in name:
            with self._lock:
                self.backend_compiles += 1

    def _on_event(self, name: str, **_kw) -> None:
        if "compilation_cache" not in name:
            return
        with self._lock:
            if "cache_hits" in name:
                self.cache_hits += 1
            elif "cache_misses" in name:
                self.cache_misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"backendCompiles": self.backend_compiles,
                    "cacheHits": self.cache_hits,
                    "cacheMisses": self.cache_misses}

    @staticmethod
    def real_compiles(since: dict, now: Optional[dict] = None,
                      meter: Optional["CompileMeter"] = None) -> int:
        """Genuine XLA backend compiles between two snapshots: duration
        events minus persistent-cache loads. Never negative (a hit's
        duration event and the hit event land in either order across
        threads)."""
        if now is None:
            now = (meter or compile_meter()).snapshot()
        return max(0, (now["backendCompiles"] - since["backendCompiles"])
                   - (now["cacheHits"] - since["cacheHits"]))


def compile_meter() -> CompileMeter:
    """The singleton meter (registered on first use)."""
    global _METER
    with _METER_LOCK:
        if _METER is None:
            _METER = CompileMeter()
        return _METER
