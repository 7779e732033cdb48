"""Durable AOT executable cache (sched/aotcache.py) — the failure menu.

Every test here is a way the cache directory can betray a restarted
scheduler: torn bytes, flipped bits, a toolchain that moved underneath
it, a manifest that didn't survive, more entries than the bound allows.
The contract under test is single: damage degrades to a COUNTED
recompile — never a crash, never a silently wrong program — and an
intact cache makes the restart genuinely zero-compile (asserted through
the compile meter, not vibes).
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from kubernetes_tpu.sched.aotcache import (
    ENTRY_SUFFIX,
    FINGERPRINT_FILE,
    MANIFEST_FILE,
    AotExecutableCache,
    PlacedCacheObserver,
    cache_knobs,
    resolve_cache_dir,
)

pytestmark = pytest.mark.disaster


@pytest.fixture
def cache_root(tmp_path):
    """A cache dir whose activation is always disarmed afterwards, so
    the process-global jax persistent-cache config never leaks into the
    next test."""
    yield str(tmp_path / "aot")
    AotExecutableCache.disarm()
    jax.clear_caches()


def _entries(cache) -> list:
    return [n for n in os.listdir(cache.entries_dir)
            if n.endswith(ENTRY_SUFFIX)]


def _populate(root: str, knobs=None, fns=1) -> int:
    """Activate a fresh cache at ``root``, run ``fns`` distinct jits so
    entries persist, seal, and return the sealed entry count."""
    cache = AotExecutableCache(root, knobs=knobs or {"k": 1})
    cache.activate()
    for i in range(fns):
        k = float(i + 2)
        jax.jit(lambda x, _k=k: x * _k + 1)(jnp.arange(8)).block_until_ready()
    n = cache.seal()
    assert n >= fns
    return n


# ---- warm restart ------------------------------------------------------------

def test_warm_restart_loads_instead_of_compiling(cache_root):
    n = _populate(cache_root)
    jax.clear_caches()  # the in-process restart: dispatch caches are gone
    cache = AotExecutableCache(cache_root, knobs={"k": 1})
    boot = cache.activate()
    assert boot["entries"] == n
    assert boot["fingerprintStale"] is False
    assert boot["corruptSwept"] == 0 and boot["rotated"] == 0
    jax.jit(lambda x: x * 2.0 + 1)(jnp.arange(8)).block_until_ready()
    stats = cache.stats()
    assert stats["realCompiles"] == 0, stats  # the headline property
    assert stats["hits"] >= 1
    assert stats["errors"] == 0 and stats["invalidations"] == 0


# ---- corruption --------------------------------------------------------------

def test_bitflip_swept_and_recompiled_not_crashed(cache_root):
    _populate(cache_root)
    jax.clear_caches()
    probe = AotExecutableCache(cache_root, knobs={"k": 1})
    victim = os.path.join(probe.entries_dir, _entries(probe)[0])
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(victim, "wb") as f:
        f.write(bytes(blob))
    cache = AotExecutableCache(cache_root, knobs={"k": 1})
    boot = cache.activate()
    assert boot["corruptSwept"] == 1 and cache.errors == 1
    assert not os.path.exists(victim)  # deleted before jax could read it
    # the program behind the swept entry recompiles and answers correctly
    got = jax.jit(lambda x: x * 2.0 + 1)(jnp.arange(8))
    assert got.tolist() == [x * 2.0 + 1 for x in range(8)]
    assert cache.stats()["errors"] == 1


def test_truncation_swept(cache_root):
    _populate(cache_root)
    probe = AotExecutableCache(cache_root, knobs={"k": 1})
    victim = os.path.join(probe.entries_dir, _entries(probe)[0])
    with open(victim, "r+b") as f:
        f.truncate(max(1, os.path.getsize(victim) // 2))
    cache = AotExecutableCache(cache_root, knobs={"k": 1})
    boot = cache.activate()
    assert boot["corruptSwept"] == 1
    assert not os.path.exists(victim)


def test_corrupt_manifest_counted_entries_survive(cache_root):
    """A garbage manifest is a counted error, but the entries are NOT
    thrown away: jax wrote them via temp+rename and its framing
    self-checks, so unverifiable-but-present beats a cold ladder."""
    n = _populate(cache_root)
    with open(os.path.join(cache_root, MANIFEST_FILE), "w") as f:
        f.write("{torn")
    cache = AotExecutableCache(cache_root, knobs={"k": 1})
    boot = cache.activate()
    assert cache.errors == 1  # reason="manifest"
    assert boot["entries"] == n and boot["corruptSwept"] == 0
    # and the re-hash re-manifested them for the NEXT boot
    doc = json.load(open(os.path.join(cache_root, MANIFEST_FILE)))
    assert len(doc["entries"]) == n


# ---- fingerprint -------------------------------------------------------------

def test_stale_fingerprint_invalidates_wholesale(cache_root):
    _populate(cache_root, knobs={"k": 1})
    jax.clear_caches()
    cache = AotExecutableCache(cache_root, knobs={"k": 2})  # knob changed
    boot = cache.activate()
    assert boot["fingerprintStale"] is True
    assert boot["entries"] == 0 and cache.invalidations >= 1
    assert _entries(cache) == []  # nothing salvaged
    # the new fingerprint is committed: the NEXT same-knob boot trusts it
    doc = json.load(open(os.path.join(cache_root, FINGERPRINT_FILE)))
    assert doc["fingerprint"] == cache.fingerprint


def test_unreadable_fingerprint_treated_as_stale(cache_root):
    _populate(cache_root)
    with open(os.path.join(cache_root, FINGERPRINT_FILE), "w") as f:
        f.write("not json")
    cache = AotExecutableCache(cache_root, knobs={"k": 1})
    boot = cache.activate()
    assert boot["fingerprintStale"] is True and boot["entries"] == 0


# ---- size bound --------------------------------------------------------------

def test_gc_rotates_past_max_bytes(cache_root):
    n = _populate(cache_root, fns=2)
    cache = AotExecutableCache(cache_root, knobs={"k": 1}, max_bytes=1)
    boot = cache.activate()
    assert boot["rotated"] == n and boot["entries"] == 0
    assert cache.invalidations == n  # counted as reason="rotation"


# ---- wiring ------------------------------------------------------------------

def test_resolve_cache_dir_env_override(monkeypatch, tmp_path):
    from kubernetes_tpu.config.types import SchedulerConfiguration
    cfg = SchedulerConfiguration.from_dict(
        {"aotCacheDir": str(tmp_path / "cfg")})
    monkeypatch.delenv("KTPU_AOT_CACHE", raising=False)
    assert resolve_cache_dir(cfg) == str(tmp_path / "cfg")
    for off in ("", "0", "off", "none", "FALSE"):
        monkeypatch.setenv("KTPU_AOT_CACHE", off)
        assert resolve_cache_dir(cfg) is None
    monkeypatch.setenv("KTPU_AOT_CACHE", str(tmp_path / "env"))
    assert resolve_cache_dir(cfg) == str(tmp_path / "env")


def test_cache_knobs_cover_lowering_config():
    from kubernetes_tpu.config.types import SchedulerConfiguration
    cfg = SchedulerConfiguration()
    knobs = cache_knobs(cfg)
    assert set(knobs) == {"meshShape", "batchSize", "maxDrainBatches"}
    # any knob change must change the fingerprint (wholesale distrust)
    from kubernetes_tpu.parallel.aot import lowering_fingerprint
    flipped = dict(knobs, batchSize=knobs["batchSize"] * 2)
    assert lowering_fingerprint(knobs) != lowering_fingerprint(flipped)


# ---- a cache placed from outside ---------------------------------------------

def _tree(root) -> dict:
    out = {}
    for d, _dirs, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out


def test_outside_cache_dir_is_never_repointed_or_cleaned(
        monkeypatch, tmp_path, cache_root):
    """JAX_COMPILATION_CACHE_DIR wins over aotCacheDir: the runner
    observes that directory — jax stays pointed at it, and nothing in it
    is deleted or rewritten, whatever this scheduler's knobs say about
    the fingerprint another program left there."""
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.store.apiserver import APIServer
    outside = tmp_path / "outside"
    outside.mkdir()
    # what another cell sharing the directory could have left behind
    (outside / f"jit_other{ENTRY_SUFFIX}").write_bytes(b"not ours")
    (outside / "jit_other-atime").write_bytes(b"0")
    (outside / FINGERPRINT_FILE).write_text('{"fingerprint": "someone-else"}')
    (outside / MANIFEST_FILE).write_text('{"entries": {}}')
    before = _tree(outside)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(outside))
    # jax reads the variable at import; this process imported it earlier
    jax.config.update("jax_compilation_cache_dir", str(outside))
    server = APIServer().start()
    try:
        cfg = SchedulerConfiguration(aot_cache_dir=cache_root, batch_size=8)
        assert resolve_cache_dir(cfg) is None
        runner = SchedulerRunner(HTTPClient(server.url), cfg)
        try:
            assert jax.config.jax_compilation_cache_dir == str(outside)
            assert runner.aot_cache is not None
            assert isinstance(runner.aot_cache, PlacedCacheObserver)
            assert runner.aot_cache.boot["entries"] == 1
            runner.aot_cache.seal(force=True)
            st = runner._aot_cache_status()
            assert st["enabled"] and st["dir"] == str(outside)
            assert st["realCompiles"] >= 0  # the meter still reads
        finally:
            runner.scheduler.close()
    finally:
        server.stop()
    assert _tree(outside) == before
    assert not os.path.exists(cache_root)  # the owned dir was never armed


def test_place_compile_cache_fixed_path_or_outside(monkeypatch, tmp_path,
                                                   cache_root):
    from kubernetes_tpu.parallel import aot
    monkeypatch.delenv(aot.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(aot, "DEFAULT_COMPILE_CACHE_DIR",
                        str(tmp_path / "fixed"))
    assert aot.place_compile_cache() == str(tmp_path / "fixed")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    # placed from outside: returned as is, jax's setting left alone
    monkeypatch.setenv(aot.CACHE_DIR_ENV, str(tmp_path / "outside"))
    assert aot.place_compile_cache() == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")
    # the default is one fixed directory inside the checkout
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.undo()
    assert os.path.dirname(aot.DEFAULT_COMPILE_CACHE_DIR) == repo
