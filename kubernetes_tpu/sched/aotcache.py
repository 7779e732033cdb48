"""Durable AOT executable cache — zero-compile *cold start*.

PR 14 proved zero XLA compiles in the fleet steady window; this module
makes the property survive the scheduler process dying. Every program
the warm ladder compiles (drain_step at each shape bucket and donated
layout, gang_schedule, preempt_wave, the fused-fold patch variants, the
tiny staging jits) is persisted as an XLA-serialized executable in a
cache directory next to the WAL; a restarted scheduler deserializes
instead of compiling, so the ~10–20s warm_drain ladder becomes a
sub-second disk load and the rolling-upgrade outage window collapses.

Mechanism: the entries themselves ride jax's persistent compilation
cache (one ``<name>-<sha256 of HLO+compile options+toolchain>-cache``
file per program), which both ``lower().compile()`` AND live jit
dispatch consult — the only seam that covers every variant, including
programs a bench never warms explicitly. What this module adds around
that directory is the durability discipline the WAL established:

  fingerprint   ``FINGERPRINT.json`` pins (jax/jaxlib versions, backend
                platform + device population, XLA flags, declared config
                knobs) via parallel/aot.lowering_fingerprint. A mismatch
                at boot invalidates the cache WHOLESALE (counted) — a
                new toolchain must never even get the chance to
                misinterpret an old toolchain's bytes.
  integrity     ``MANIFEST.json`` records each entry's size + sha256 at
                seal time. The boot scan deletes (and counts, under
                ``scheduler_aot_cache_errors_total``) any truncated,
                bit-flipped or unmanifested entry BEFORE jax can read it
                — a rejected entry degrades to a recompile, never a
                crash, never a wrong program.
  atomicity     fingerprint and manifest commit through
                utils/atomicio.atomic_write (temp file + fsync + rename
                — the WAL's commit discipline; ktpu-lint KTL008 enforces
                the helper).
  bound         a size/rotation GC evicts oldest-read entries past
                ``max_bytes`` (counted as ``reason="rotation"``).

Ownership: all of the above applies ONLY to a directory the operator
gave this scheduler (``aotCacheDir``). Where the cache was placed from
outside — ``JAX_COMPILATION_CACHE_DIR``, or an entry point's fixed
default (parallel/aot.place_compile_cache) — the directory may be shared
with other programs and other configurations, so the runner attaches a
``PlacedCacheObserver`` instead: it never re-points jax, writes no
fingerprint or manifest, and deletes nothing; the compile meter, the
boot entry count (which still arms the first-drain canary) and the
``ktpu status`` line work the same.

Correctness backstop: a loaded executable is canary-checked on first
use — the runner forces the ParitySentinel to sample the FIRST drain
dispatch after a warm-from-cache boot, so a wrong program trips the
device circuit breaker with ``reason="parity"`` before a second batch
is judged by it.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import time
from typing import Optional

from kubernetes_tpu.metrics.registry import (
    AOT_CACHE_BOOT_MS,
    AOT_CACHE_BYTES,
    AOT_CACHE_ENTRIES,
    AOT_CACHE_ERRORS,
    AOT_CACHE_INVALIDATIONS,
)
from kubernetes_tpu.parallel.aot import (
    compile_meter,
    external_cache_dir,
    lowering_fingerprint,
    persist_every_program,
    point_jax_cache_at,
)
from kubernetes_tpu.utils.atomicio import atomic_write_json

_LOG = logging.getLogger(__name__)

FINGERPRINT_FILE = "FINGERPRINT.json"
MANIFEST_FILE = "MANIFEST.json"
ENTRY_SUFFIX = "-cache"          # jax file_system_cache entry files
ATIME_SUFFIX = "-atime"          # jax's read-time sidecars (not entries)
DEFAULT_MAX_BYTES = 512 * 1024 * 1024


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


class AotExecutableCache:
    """One managed executable-cache directory (``root/entries`` +
    fingerprint + manifest). ``activate()`` arms it process-wide;
    ``seal()`` commits the manifest after the warm ladder has populated
    new entries."""

    def __init__(self, root: str, knobs: Optional[dict] = None,
                 max_bytes: int = DEFAULT_MAX_BYTES):
        self.root = os.path.abspath(root)
        self.entries_dir = os.path.join(self.root, "entries")
        self.knobs = dict(knobs or {})
        self.max_bytes = int(max_bytes)
        self.fingerprint = lowering_fingerprint(self.knobs)
        self.active = False
        # counted degrades (mirrored into the registry metrics; kept as
        # plain ints too so one cache instance's stats don't read another
        # incarnation's process-wide counters)
        self.errors = 0          # corrupt/unreadable entries deleted
        self.invalidations = 0   # fingerprint wholesale + rotation GC
        self.boot: dict = {}     # last activate() report
        self._meter_base: Optional[dict] = None
        self._sealed_sig: Optional[tuple] = None

    # ---- boot ------------------------------------------------------------

    def activate(self) -> dict:
        """Fingerprint-check, integrity-scan, GC and ARM the cache (points
        jax's persistent compilation cache at ``entries/``). Returns the
        boot report also kept as ``self.boot``. Never raises on cache
        damage — every rejected entry is a counted recompile, and a
        cache too broken to scan is invalidated wholesale."""
        t0 = time.monotonic()  # ktpu-lint: disable=KTL003 -- boot-duration measurement (reported ms), not time-window logic a FakeClock would need to advance
        os.makedirs(self.entries_dir, exist_ok=True)
        stale = self._fingerprint_stale()
        if stale:
            self._invalidate_all(reason="fingerprint")
        manifest = self._load_manifest()
        kept, swept = self._integrity_scan(manifest)
        rotated = self._gc(kept)
        for name in rotated:
            kept.pop(name, None)
        self._commit_meta(kept)
        self._arm_jax()
        self._meter_base = compile_meter().snapshot()
        n_bytes = sum(e["bytes"] for e in kept.values())
        self.boot = {
            "entries": len(kept),
            "bytes": n_bytes,
            "loadMs": round((time.monotonic() - t0) * 1000.0, 1),  # ktpu-lint: disable=KTL003 -- same boot-duration measurement as t0 above
            "fingerprintStale": stale,
            "corruptSwept": swept,
            "rotated": len(rotated),
        }
        AOT_CACHE_ENTRIES.set(len(kept))
        AOT_CACHE_BYTES.set(n_bytes)
        AOT_CACHE_BOOT_MS.set(self.boot["loadMs"])
        self.active = True
        _LOG.info(
            "AOT executable cache armed at %s: %d entries (%.1f KB) in "
            "%sms%s%s", self.root, len(kept), n_bytes / 1e3,
            self.boot["loadMs"],
            f", {swept} corrupt swept" if swept else "",
            " after WHOLESALE fingerprint invalidation" if stale else "")
        return self.boot

    def _fingerprint_stale(self) -> bool:
        path = os.path.join(self.root, FINGERPRINT_FILE)
        try:
            with open(path) as f:
                recorded = json.load(f).get("fingerprint")
        except FileNotFoundError:
            return False  # first boot: nothing to distrust
        except (OSError, ValueError):
            return True   # unreadable fingerprint = unverifiable cache
        return recorded != self.fingerprint

    def _invalidate_all(self, reason: str) -> None:
        """Wholesale: every entry (and sidecar) goes; the manifest goes
        with them. A stale-toolchain cache is dead bytes at best and a
        miscompile risk at worst — partial salvage is not worth it."""
        n = 0
        for name in self._listdir():
            try:
                os.unlink(os.path.join(self.entries_dir, name))
                if name.endswith(ENTRY_SUFFIX):
                    n += 1
            except OSError:
                pass
        try:
            os.unlink(os.path.join(self.root, MANIFEST_FILE))
        except OSError:
            pass
        self.invalidations += n
        AOT_CACHE_INVALIDATIONS.inc({"reason": reason}, by=max(n, 1))
        _LOG.warning("AOT cache %s: %d entries invalidated wholesale "
                     "(%s)", self.root, n, reason)

    def _load_manifest(self) -> dict:
        path = os.path.join(self.root, MANIFEST_FILE)
        try:
            with open(path) as f:
                doc = json.load(f)
            return dict(doc.get("entries") or {})
        except FileNotFoundError:
            return {}
        except (OSError, ValueError):
            # an unreadable manifest means NO entry is verifiable; treat
            # every present entry as unmanifested (the scan sweeps them)
            AOT_CACHE_ERRORS.inc({"reason": "manifest"})
            self.errors += 1
            return {}

    def _integrity_scan(self, manifest: dict) -> tuple[dict, int]:
        """Every on-disk entry either matches its manifest checksum or is
        deleted before jax can deserialize it. Unmanifested entries (a
        crash between entry write and seal) are kept but re-hashed — jax
        wrote them through its own temp+rename, and its zstd framing
        self-checks; the manifest exists to catch the torn/flipped bytes
        that framing can miss and to pin what seal() saw."""
        kept: dict = {}
        swept = 0
        for name in self._listdir(ENTRY_SUFFIX):
            path = os.path.join(self.entries_dir, name)
            try:
                digest = _sha256_file(path)
                size = os.path.getsize(path)
            except OSError:
                self._sweep_entry(name, "unreadable")
                swept += 1
                continue
            want = manifest.get(name)
            if want is not None and (want.get("sha256") != digest
                                     or want.get("bytes") != size):
                self._sweep_entry(name, "corrupt")
                swept += 1
                continue
            kept[name] = {"sha256": digest, "bytes": size,
                          "sealed": (want or {}).get("sealed", False)}
        return kept, swept

    def _sweep_entry(self, name: str, reason: str) -> None:
        self.errors += 1
        AOT_CACHE_ERRORS.inc({"reason": reason})
        for victim in (name, name[:-len(ENTRY_SUFFIX)] + ATIME_SUFFIX):
            try:
                os.unlink(os.path.join(self.entries_dir, victim))
            except OSError:
                pass
        _LOG.warning("AOT cache entry %s rejected (%s) — deleted; the "
                     "program recompiles on first use", name, reason)

    def _gc(self, kept: dict) -> list[str]:
        """Size bound: evict oldest-read entries (jax's -atime sidecar,
        falling back to mtime) until under ``max_bytes``."""
        total = sum(e["bytes"] for e in kept.values())
        if total <= self.max_bytes:
            return []

        def read_ts(name: str) -> float:
            base = os.path.join(self.entries_dir,
                                name[:-len(ENTRY_SUFFIX)])
            for p in (base + ATIME_SUFFIX,
                      os.path.join(self.entries_dir, name)):
                try:
                    return os.path.getmtime(p)
                except OSError:
                    continue
            return 0.0

        rotated: list[str] = []
        for name in sorted(kept, key=read_ts):
            if total <= self.max_bytes:
                break
            total -= kept[name]["bytes"]
            for victim in (name, name[:-len(ENTRY_SUFFIX)] + ATIME_SUFFIX):
                try:
                    os.unlink(os.path.join(self.entries_dir, victim))
                except OSError:
                    pass
            rotated.append(name)
        if rotated:
            self.invalidations += len(rotated)
            AOT_CACHE_INVALIDATIONS.inc({"reason": "rotation"},
                                        by=len(rotated))
            _LOG.info("AOT cache rotated %d entries past the %d-byte "
                      "bound", len(rotated), self.max_bytes)
        return rotated

    def _commit_meta(self, entries: dict) -> None:
        atomic_write_json(os.path.join(self.root, FINGERPRINT_FILE),
                          {"fingerprint": self.fingerprint,
                           "knobs": self.knobs}, indent=1, default=str)
        atomic_write_json(os.path.join(self.root, MANIFEST_FILE),
                          {"entries": entries}, indent=1)
        self._sealed_sig = self._dir_sig()

    def _arm_jax(self) -> None:
        point_jax_cache_at(self.entries_dir)
        persist_every_program()

    @staticmethod
    def disarm() -> None:
        """Detach jax from any cache directory (tests restore the
        process-global default)."""
        point_jax_cache_at(None)

    # ---- steady state ----------------------------------------------------

    def _listdir(self, suffix: str = "") -> list[str]:
        try:
            return sorted(n for n in os.listdir(self.entries_dir)
                          if n.endswith(suffix))
        except OSError:
            return []

    def _dir_sig(self) -> tuple:
        return tuple((n, self._size(n)) for n in self._listdir(ENTRY_SUFFIX))

    def _size(self, name: str) -> int:
        try:
            return os.path.getsize(os.path.join(self.entries_dir, name))
        except OSError:
            return 0

    def seal(self, force: bool = False) -> int:
        """Re-hash and commit the manifest for the CURRENT entry set —
        called after the warm ladder (and on the status cadence) so
        entries jax wrote since the last seal become verifiable at the
        next boot. Cheap no-op when the entry set hasn't changed.
        Returns the number of manifested entries."""
        if not self.active:
            return 0
        if not force and self._dir_sig() == self._sealed_sig:
            return len(self._sealed_sig or ())
        entries: dict = {}
        for name in self._listdir(ENTRY_SUFFIX):
            path = os.path.join(self.entries_dir, name)
            try:
                entries[name] = {"sha256": _sha256_file(path),
                                 "bytes": os.path.getsize(path),
                                 "sealed": True}
            except OSError:
                continue  # racing eviction; next seal re-judges
        try:
            self._commit_meta(entries)
        except OSError:
            self.errors += 1
            AOT_CACHE_ERRORS.inc({"reason": "io"})
            _LOG.warning("AOT cache manifest commit failed", exc_info=True)
            return len(entries)
        AOT_CACHE_ENTRIES.set(len(entries))
        AOT_CACHE_BYTES.set(sum(e["bytes"] for e in entries.values()))
        return len(entries)

    def stats(self) -> dict:
        """Status-surface block (``ktpu status`` renders it; the
        scheduler-kill bench gates on ``realCompiles``). Hits/misses are
        THIS activation's persistent-cache traffic; ``realCompiles`` is
        genuine XLA work since activation — 0 after a warm boot is the
        zero-compile-cold-start property itself."""
        entries = self._listdir(ENTRY_SUFFIX)
        stats = {"enabled": True, "dir": self.root,
                 "entries": len(entries),
                 "bytes": sum(self._size(n) for n in entries),
                 "errors": self.errors,
                 "invalidations": self.invalidations,
                 "bootEntries": self.boot.get("entries"),
                 "bootLoadMs": self.boot.get("loadMs")}
        stats.update(_meter_since(self._meter_base))
        return stats


def _meter_since(base: Optional[dict]) -> dict:
    """Persistent-cache traffic and genuine compiles since ``base``."""
    if base is None:
        return {}
    now = compile_meter().snapshot()
    return {"hits": now["cacheHits"] - base["cacheHits"],
            "misses": now["cacheMisses"] - base["cacheMisses"],
            "realCompiles": compile_meter().real_compiles(base, now)}


class PlacedCacheObserver:
    """A compile-cache directory jax was pointed at from OUTSIDE
    (``JAX_COMPILATION_CACHE_DIR``, or an entry point's
    place_compile_cache): other programs and configurations may share it,
    so it is only observed — entry count, bytes, the compile meter — and
    never re-pointed, written to or cleaned. The surface the runner uses
    on AotExecutableCache: ``activate()`` -> ``boot``, ``seal()``,
    ``stats()``."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.boot: dict = {}
        self._meter_base: Optional[dict] = None

    def _entries(self) -> tuple[int, int]:
        try:
            sizes = [e.stat().st_size for e in os.scandir(self.root)
                     if e.name.endswith(ENTRY_SUFFIX)]
        except OSError:
            sizes = []
        return len(sizes), sum(sizes)

    def activate(self) -> dict:
        n, n_bytes = self._entries()
        self._meter_base = compile_meter().snapshot()
        self.boot = {"entries": n, "bytes": n_bytes, "loadMs": 0.0}
        _LOG.info("compile cache placed from outside at %s, observing "
                  "only: %d entries (%.1f KB)", self.root, n, n_bytes / 1e3)
        return self.boot

    def seal(self, force: bool = False) -> int:
        return self._entries()[0]  # nothing of ours to commit

    def stats(self) -> dict:
        n, n_bytes = self._entries()
        return {"enabled": True, "dir": self.root, "entries": n,
                "bytes": n_bytes, "errors": 0, "invalidations": 0,
                "bootEntries": self.boot.get("entries"),
                "bootLoadMs": self.boot.get("loadMs"),
                **_meter_since(self._meter_base)}


def resolve_cache_dir(cfg) -> Optional[str]:
    """The cache directory this scheduler OWNS: ``KTPU_AOT_CACHE``
    overrides config (``"0"``/``"off"`` disable; any other value is a
    path), else ``cfg.aot_cache_dir``; None = none owned (the tier-1
    default). Always None under ``JAX_COMPILATION_CACHE_DIR`` — a cache
    placed from outside is never re-pointed (see ``runner_cache``)."""
    if external_cache_dir() is not None:
        return None
    env = os.environ.get("KTPU_AOT_CACHE")
    if env is not None:
        s = env.strip()
        if s.lower() in ("", "0", "off", "none", "false"):
            return None
        return s
    return getattr(cfg, "aot_cache_dir", None)


def runner_cache(cfg):
    """The cache object a SchedulerRunner boots with, not yet activated:
    the owned ``aotCacheDir`` with its full discipline, else an observer
    over whatever directory jax was pointed at from outside (the
    environment variable, or an entry point's place_compile_cache), else
    None (library construction under tier-1)."""
    owned = resolve_cache_dir(cfg)
    if owned:
        return AotExecutableCache(
            owned, knobs=cache_knobs(cfg),
            max_bytes=cfg.aot_cache_max_mb * 1024 * 1024)
    import jax
    placed = jax.config.jax_compilation_cache_dir
    return PlacedCacheObserver(placed) if placed else None


def cache_knobs(cfg) -> dict:
    """Config knobs that change lowering enough to distrust old entries
    wholesale. jax's own entry keys already cover the HLO and compile
    options, so this list is the coarse outer guard, not the dedup key."""
    return {"meshShape": list(cfg.mesh_shape) if cfg.mesh_shape else None,
            "batchSize": int(cfg.batch_size),
            "maxDrainBatches": int(cfg.max_drain_batches)}
