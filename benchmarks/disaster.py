"""DisasterChurn: a control-plane process dies (SIGKILL) under live
churn and the whole stack survives its restart.

Two legs (``BENCH_DISASTER_CASE`` selects; default ``apiserver``):

  apiserver       the durable apiserver subprocess is killed and
                  restarted from its WAL (``run_disaster_churn``).
  scheduler-kill  the SCHEDULER subprocess is killed mid-churn and
                  restarted against the surviving apiserver
                  (``run_scheduler_kill``): with the durable AOT
                  executable cache configured, the restarted process
                  boots warm from disk — the recovery window must show
                  ZERO genuine XLA compiles (the child's compile meter
                  is the witness; a missing number is a failure), first
                  bind within seconds of loop-live, no duplicate binds,
                  no stale nominations, 0 invariant violations under a
                  fail-fast auditor running INSIDE the restarted child.

The canonical control-plane robustness scenario (upstream treats
etcd/apiserver restart + mass node-unready fallout as exactly this): a
hollow fleet heartbeats and runs pods, the scheduler binds a sustained
churn stream, the node-lifecycle controller watches for staleness — and
mid-window the apiserver subprocess is SIGKILLed, then restarted from
the SAME ``data_dir`` (WAL + snapshot replay, ``/readyz`` 503 until
done) on the SAME port. Every layer must heal through its own
discipline: HTTPClient full-jitter backoff absorbs the refused-
connection storm, informers relist (410/TooOld on pre-restart rvs),
fleet batchers back off + re-coalesce + re-assert on reconnect, and the
node-lifecycle disruption mode keeps the fleet-wide lease staleness the
outage manufactured from cascading into a taint/evict storm.

Hard gates (missing number = failure, the PR-8 SLO discipline):
  - every pod that exists at the end is BOUND (none lost, none stuck)
  - 0 confirmed invariant violations (fail-fast auditor live throughout)
  - 0 outage-caused evictions, 0 lifecycle taints left on any node —
    with the disruption mode provably ENGAGED during the outage and
    RELEASED after heal (protection that never fires protects nothing)
  - time-to-first-bind-after-restart <= ``bind_slo_s`` (default 10s)
  - the restarted server reached /readyz 200 (replay completed)
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time


def _pod_churn_loop(client, stop, counter, period_s: float = 0.25) -> None:
    """Sustained pod churn (namespace ``churn``): a rolling window of
    short-lived pods. Errors are EXPECTED mid-outage (the apiserver is
    dead); the loop keeps trying and counts what committed."""
    import itertools

    from kubernetes_tpu.testing.wrappers import make_pod
    seq = itertools.count()
    live: list = []
    while not stop.is_set():
        i = next(seq)
        try:
            pod = make_pod(f"churn-p{i}", "churn").req(
                {"cpu": "100m"}).obj()
            client.pods("churn").create(pod.to_dict())
            live.append(pod.metadata.name)
            if len(live) > 4:
                client.pods("churn").delete(live.pop(0))
            counter["ops"] = counter.get("ops", 0) + 2
        except Exception:
            counter["errors"] = counter.get("errors", 0) + 1
        stop.wait(period_s)


def _unbound(client, namespaces=("default", "churn")) -> list[str]:
    out = []
    for ns in namespaces:
        for p in client.pods(ns).list():
            if not (p.get("spec") or {}).get("nodeName"):
                out.append(f"{ns}/{p['metadata']['name']}")
    return out


def _lifecycle_taints(client) -> list[str]:
    from kubernetes_tpu.controllers.nodelifecycle import (
        TAINT_NOT_READY, TAINT_UNREACHABLE)
    out = []
    for n in client.nodes().list():
        for t in (n.get("spec") or {}).get("taints") or []:
            if t.get("key") in (TAINT_NOT_READY, TAINT_UNREACHABLE):
                out.append(f"{n['metadata']['name']}:{t['key']}")
    return out


def run_disaster_churn(n_hollow: int = 48, n_pods: int = 96,
                       outage_s: float = 16.0, grace_s: float = 12.0,
                       heartbeat_period: float = 1.0,
                       bind_slo_s: float = 10.0,
                       settle_timeout: float = 120.0,
                       timeout: float = 240.0,
                       log=lambda *a: None) -> dict:
    from benchmarks.connected import _audit_close, _bench_auditor
    from kubernetes_tpu.chaos.apiserver import ApiServerProcess
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.client.informer import InformerFactory
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.controllers.nodelifecycle import (
        MODE_NORMAL, NodeLifecycleController)
    from kubernetes_tpu.kubelet.kubemark import HollowCluster
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.testing.wrappers import make_pod

    # grace must clear the fleet's lease cadence (min(10, hb*5)) with
    # margin, or steady state itself flaps unready under suite load
    lease_period = min(10.0, heartbeat_period * 5)
    assert grace_s > 2 * lease_period, \
        f"grace {grace_s}s too tight for lease period {lease_period}s"

    data_dir = tempfile.mkdtemp(prefix="ktpu-disaster-")
    result: dict = {"case": "DisasterChurn",
                    "workload": f"{n_hollow}hollow_{n_pods}pods"
                                f"_{outage_s}s_outage",
                    "outage_s": outage_s, "grace_s": grace_s,
                    "data_dir_mode": True}
    failures: list[str] = []
    proc = cluster = runner = ctrl = factory = None
    churn_stop = threading.Event()
    try:
        proc = ApiServerProcess(data_dir=data_dir)
        proc.start()
        result["readyz_cold_s"] = round(proc.wait_ready(60.0), 3)
        url = proc.url

        t0 = time.time()
        cluster = HollowCluster(
            HTTPClient(url, timeout=60.0), n_hollow, prefix="dz",
            heartbeat_period=heartbeat_period).start(wait_sync=60.0)
        result["register_s"] = round(time.time() - t0, 2)
        log(f"  {n_hollow} hollow nodes registered in "
            f"{result['register_s']}s")

        # node lifecycle with DISRUPTION PROTECTION: the outage makes
        # every lease stale past grace at once — exactly the mass-unready
        # signal the partial/full-disruption modes exist to distrust
        ctrl = NodeLifecycleController(
            HTTPClient(url, timeout=30.0), grace_period=grace_s,
            monitor_period=0.5)
        factory = InformerFactory(ctrl.client)
        ctrl.register(factory)
        factory.start_all()
        assert factory.wait_for_cache_sync(30.0)
        ctrl.start()

        runner = SchedulerRunner(
            HTTPClient(url),
            SchedulerConfiguration(batch_size=64, max_drain_batches=2))
        runner.auditor = _bench_auditor(runner, HTTPClient(url))
        runner.start(wait_sync=60.0)

        client = HTTPClient(url, timeout=60.0)
        pods = [make_pod(f"dz-{i}", "default")
                .req({"cpu": "100m", "memory": "64Mi"}).obj().to_dict()
                for i in range(n_pods)]
        t_bind = time.time()
        client.pods("default").create_many(pods)
        deadline = t_bind + timeout
        while time.time() < deadline:
            if not _unbound(client, ("default",)):
                break
            time.sleep(0.25)
        result["initial_bind_s"] = round(time.time() - t_bind, 2)
        log(f"  initial {n_pods} pods bound at "
            f"+{result['initial_bind_s']}s")

        churn_stats: dict = {}
        threading.Thread(target=_pod_churn_loop,
                         args=(HTTPClient(url, timeout=30.0), churn_stop,
                               churn_stats),
                         daemon=True).start()
        time.sleep(4.0)  # churn warm-up: steady state before the crash

        # ---- the disaster -----------------------------------------------
        evictions_before = ctrl.evictions
        engaged_before = ctrl.engaged_count
        log(f"  SIGKILL apiserver (pid alive={proc.alive}); "
            f"outage {outage_s}s ...")
        t_kill = time.time()
        proc.kill()
        time.sleep(outage_s)
        modes_during = ctrl.mode
        try:
            restart_ready_s = proc.restart(ready_timeout=60.0)
            result["readyz_restart_s"] = round(restart_ready_s, 3)
        except Exception as e:
            failures.append(f"restart never reached /readyz 200: {e}")
            raise
        result["outage_total_s"] = round(time.time() - t_kill, 2)
        log(f"  restarted from WAL in {result['readyz_restart_s']}s "
            f"(mode during outage: {modes_during})")

        # time-to-first-bind-after-restart: a fresh probe pod through the
        # full heal path (informer relist -> queue -> drain -> bind)
        probe = make_pod("probe-restart", "default").req(
            {"cpu": "100m"}).obj().to_dict()
        t_probe = time.time()
        probe_client = HTTPClient(url, timeout=30.0, retry_attempts=6)
        probe_client.pods("default").create(probe)
        bound_at = None
        while time.time() - t_probe < max(bind_slo_s * 3, 30.0):
            try:
                p = probe_client.pods("default").get("probe-restart")
            except Exception:
                time.sleep(0.2)  # reconnect blip; the poll budget absorbs it
                continue
            if (p.get("spec") or {}).get("nodeName"):
                bound_at = time.time() - t_probe
                break
            time.sleep(0.2)
        result["first_bind_after_restart_s"] = (
            round(bound_at, 2) if bound_at is not None else None)
        log(f"  probe pod bound {result['first_bind_after_restart_s']}s "
            "after restart")

        # ---- heal + settle ----------------------------------------------
        settle_deadline = time.time() + settle_timeout
        while time.time() < settle_deadline and ctrl.mode != MODE_NORMAL:
            time.sleep(0.5)
        churn_stop.set()
        time.sleep(1.0)
        while time.time() < settle_deadline:
            # converged = every pod bound AND no lifecycle taint residue
            # (a 409-delayed taint removal retries on the next sweep —
            # give it the chance instead of failing on a snapshot race)
            if not _unbound(client) and not _lifecycle_taints(client):
                break
            time.sleep(0.5)
        unbound = _unbound(client)
        result["unbound"] = unbound[:20]
        result["churn_api_ops"] = churn_stats.get("ops", 0)
        result["churn_errors"] = churn_stats.get("errors", 0)
        result["fleet"] = cluster.fleet_stats()
        result["disruption"] = ctrl.disruption_status()
        taints = _lifecycle_taints(client)
        result["lifecycle_taints"] = taints[:20]
        result["outage_evictions"] = ctrl.evictions - evictions_before
        result.update(_audit_close(runner))

        # ---- the gates (missing number = failure) -----------------------
        if unbound:
            failures.append(f"{len(unbound)} pods never bound after the "
                            f"restart (first: {unbound[:5]})")
        fb = result["first_bind_after_restart_s"]
        if not isinstance(fb, (int, float)):
            failures.append("time-to-first-bind-after-restart missing — "
                            "the probe pod never bound")
        elif fb > bind_slo_s:
            failures.append(f"first bind after restart took {fb}s "
                            f"(gate {bind_slo_s}s)")
        if result["outage_evictions"]:
            failures.append(f"{result['outage_evictions']} outage-caused "
                            "evictions (disruption mode failed)")
        if taints:
            failures.append(f"lifecycle taints survived the heal: "
                            f"{taints[:5]}")
        if ctrl.engaged_count <= engaged_before:
            failures.append("disruption mode never engaged — the outage "
                            "was not observed as mass-unready (protection "
                            "untested = failure)")
        if ctrl.mode != MODE_NORMAL:
            failures.append(f"disruption mode never released "
                            f"(still {ctrl.mode})")
        if result.get("invariant_violations"):
            failures.append(f"{result['invariant_violations']} confirmed "
                            "invariant violations")
        if "readyz_restart_s" not in result:
            failures.append("readyz-after-restart missing")
    except Exception as e:  # a dead bench must fail loudly, not silently
        failures.append(f"bench crashed: {type(e).__name__}: {e}")
        result.setdefault("invariant_violations", None)
    finally:
        churn_stop.set()
        for closer in (
                (lambda: runner.stop()) if runner is not None else None,
                (lambda: ctrl.stop()) if ctrl is not None else None,
                (lambda: factory.stop_all()) if factory is not None else None,
                (lambda: cluster.stop()) if cluster is not None else None,
                (lambda: proc.stop()) if proc is not None else None):
            if closer is not None:
                try:
                    closer()
                except Exception:
                    pass
        shutil.rmtree(data_dir, ignore_errors=True)
    result["slo_failures"] = failures
    return result


def run_scheduler_kill(n_nodes: int = 16, n_pods: int = 48,
                       churn_s: float = 4.0, bind_slo_s: float = 3.0,
                       settle_timeout: float = 120.0,
                       timeout: float = 240.0,
                       ready_timeout: float = 300.0,
                       log=lambda *a: None) -> dict:
    """The scheduler dies under churn; its successor must boot warm.

    The apiserver survives (in-process, stable port, durable data_dir);
    a SchedulerProcess child — AOT cache dir on the same durable disk,
    fail-fast auditor at a 1s cadence — binds an initial workload, cold
    boot populating the executable cache. Mid pod-churn the child is
    SIGKILLed and restarted; the successor's boot report must show
    entries loaded from disk, and its gates (read over the pipe from the
    CHILD's own meters) are hard:

      - first bind <= ``bind_slo_s`` after the restarted loop is live
      - ZERO genuine XLA compiles in the child (realCompiles, compile
        meter; missing number = failure)
      - persistent-cache hits > 0 (a zero-compile claim with zero hits
        means nothing device-shaped ran — untested protection = failure)
      - 0 confirmed invariant violations, no pod lost or left unbound
        (covers duplicate binds and stale-state mistakes post-resync)

    The one leg that IGNORES ``JAX_COMPILATION_CACHE_DIR``, by design: its
    first child must boot cold, so the cache is an owned ``aotCacheDir``
    under this run's ``mkdtemp`` and the variable is withheld from the
    children (an outside cache would be warm from some earlier run).
    """
    from kubernetes_tpu.chaos.apiserver import InProcessApiServer
    from kubernetes_tpu.chaos.scheduler import SchedulerProcess
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    from kubernetes_tpu.parallel.aot import CACHE_DIR_ENV
    data_dir = tempfile.mkdtemp(prefix="ktpu-schedkill-")
    outside_cache = os.environ.pop(CACHE_DIR_ENV, None)  # spawn inherits env
    result: dict = {"case": "SchedulerKill",
                    "workload": f"{n_nodes}nodes_{n_pods}pods"}
    failures: list[str] = []
    server = sched = None
    churn_stop = threading.Event()
    try:
        server = InProcessApiServer(data_dir=os.path.join(data_dir, "api"))
        server.start()
        url = server.url
        seed_client = HTTPClient(url, timeout=60.0)
        seed_client.nodes().create_many([
            make_node(f"sk-n{i}").capacity(
                {"cpu": "8", "memory": "16Gi", "pods": "64"}).obj().to_dict()
            for i in range(n_nodes)])

        sched = SchedulerProcess(
            url,
            cfg={"aotCacheDir": os.path.join(data_dir, "aot-cache"),
                 "auditFailFast": True, "auditIntervalSeconds": 1.0,
                 "batchSize": 16,
                 "backoffInitialSeconds": 0.05, "backoffMaxSeconds": 0.5},
            warm={"pods": 16, "requests": {"cpu": "100m",
                                           "memory": "64Mi"}})
        t0 = time.time()
        ready_cold = sched.start(ready_timeout=ready_timeout)
        result["cold_boot_s"] = round(time.time() - t0, 2)
        result["cold_ready"] = ready_cold
        log(f"  cold scheduler boot {result['cold_boot_s']}s "
            f"(warm ladder {ready_cold['warmMs']}ms, cache boot "
            f"{ready_cold.get('aotCacheBoot')})")

        t_bind = time.time()
        seed_client.pods("default").create_many(
            [make_pod(f"sk-{i}", "default")
             .req({"cpu": "100m", "memory": "64Mi"}).obj().to_dict()
             for i in range(n_pods)])
        deadline = t_bind + timeout
        while time.time() < deadline:
            if not _unbound(seed_client, ("default",)):
                break
            time.sleep(0.2)
        result["initial_bind_s"] = round(time.time() - t_bind, 2)
        log(f"  initial {n_pods} pods bound at "
            f"+{result['initial_bind_s']}s")

        churn_stats: dict = {}
        threading.Thread(target=_pod_churn_loop,
                         args=(HTTPClient(url, timeout=30.0), churn_stop,
                               churn_stats),
                         daemon=True).start()
        time.sleep(churn_s / 2)

        # Compile quiescence before the kill: churn-driven shape buckets
        # (patch write widths, mostly) compile lazily, and jax persists
        # each entry only when its compile finishes — killing mid-ladder
        # would test an incomplete cache, which is a different (weaker)
        # claim than the one gated here: a STEADY-STATE scheduler's
        # restart is zero-compile. Poll the child's meter until the entry
        # set and compile count stop moving.
        prev = None
        quiesce_deadline = time.time() + 30.0
        while time.time() < quiesce_deadline:
            s = sched.stats()
            cur = (s["aotCache"].get("entries"),
                   s["aotCache"].get("realCompiles"))
            if cur == prev:
                break
            prev = cur
            time.sleep(0.7)
        result["steady_cache_entries"] = prev[0] if prev else None

        # ---- the disaster -----------------------------------------------
        log(f"  SIGKILL scheduler (pid alive={sched.alive}) mid-churn, "
            f"{prev[0] if prev else '?'} entries persisted ...")
        sched.kill()
        time.sleep(churn_s / 2)  # churn piles up against no scheduler
        try:
            restart_s = sched.restart(ready_timeout=ready_timeout)
        except Exception as e:
            failures.append(f"scheduler restart never became ready: {e}")
            raise
        ready_warm = sched.ready
        result["restart_total_s"] = round(restart_s, 2)
        result["warm_ready"] = ready_warm
        cache_boot = ready_warm.get("aotCacheBoot") or {}
        result["warm_boot_entries"] = cache_boot.get("entries")
        log(f"  scheduler restarted in {restart_s:.1f}s total; warm "
            f"ladder {ready_warm['warmMs']}ms from "
            f"{cache_boot.get('entries')} cached entries "
            f"({cache_boot.get('loadMs')}ms cache load)")

        # first bind after the restarted loop is live: a fresh probe pod
        # through the full path (informer -> queue -> drain -> bind)
        probe = make_pod("probe-schedkill", "default").req(
            {"cpu": "100m"}).obj().to_dict()
        t_probe = time.time()
        seed_client.pods("default").create(probe)
        bound_at = None
        while time.time() - t_probe < max(bind_slo_s * 5, 30.0):
            p = seed_client.pods("default").get("probe-schedkill")
            if (p.get("spec") or {}).get("nodeName"):
                bound_at = time.time() - t_probe
                break
            time.sleep(0.1)
        result["first_bind_after_restart_s"] = (
            round(bound_at, 2) if bound_at is not None else None)
        log(f"  probe pod bound {result['first_bind_after_restart_s']}s "
            "after restart-ready")

        # The zero-compile gate reads the meter NOW — the recovery window
        # (activation -> warm ladder -> loop -> first bind) is what the
        # cache promises is compile-free. Churn after this point may
        # legitimately surface a shape bucket the predecessor never saw.
        try:
            recovery = sched.stats()
            result["recovery_stats"] = recovery
        except Exception as e:
            recovery = {}
            failures.append(f"recovery-window stats unavailable: {e} — "
                            "the zero-compile gate is unverifiable")
        cache_stats = recovery.get("aotCache") or {}

        # ---- settle + the child's end-state numbers ---------------------
        churn_stop.set()
        time.sleep(1.0)
        settle_deadline = time.time() + settle_timeout
        while time.time() < settle_deadline:
            if not _unbound(seed_client):
                break
            time.sleep(0.25)
        unbound = _unbound(seed_client)
        result["unbound"] = unbound[:20]
        result["churn_api_ops"] = churn_stats.get("ops", 0)
        result["churn_errors"] = churn_stats.get("errors", 0)
        try:
            stats = sched.stats()
            result["child_stats"] = stats
        except Exception as e:
            stats = {}
            failures.append(f"child stats unavailable: {e} — every gate "
                            "below it is unverifiable")
        result["invariant_violations"] = stats.get("violations")

        # ---- the gates (missing number = failure) -----------------------
        if unbound:
            failures.append(f"{len(unbound)} pods never bound after the "
                            f"scheduler restart (first: {unbound[:5]})")
        fb = result["first_bind_after_restart_s"]
        if not isinstance(fb, (int, float)):
            failures.append("time-to-first-bind-after-restart missing — "
                            "the probe pod never bound")
        elif fb > bind_slo_s:
            failures.append(f"first bind after restart took {fb}s "
                            f"(gate {bind_slo_s}s)")
        if not isinstance(result["warm_boot_entries"], int) \
                or result["warm_boot_entries"] < 1:
            failures.append("restarted scheduler loaded no cached "
                            "executables — the warm-from-birth path "
                            "never ran (untested protection = failure)")
        rc = cache_stats.get("realCompiles")
        if not isinstance(rc, int):
            failures.append("genuine-compile count missing from the "
                            "restarted child (zero-compile gate "
                            "unverifiable = failure)")
        elif rc > 0:
            failures.append(f"{rc} genuine XLA compiles in the recovery "
                            "window (gate: 0 — the executable cache "
                            "missed)")
        if prev is not None and isinstance(prev[1], int) and prev[1] == 0:
            failures.append("the COLD child reported 0 genuine compiles — "
                            "the meter is not seeing compiles, so the "
                            "warm child's 0 proves nothing")
        if not cache_stats.get("hits"):
            failures.append("0 persistent-cache hits in the restarted "
                            "child — nothing loaded from disk, the "
                            "zero-compile number proves nothing")
        if cache_stats.get("bootLoadMs") is None:
            failures.append("cache boot-load timing missing")
        if stats.get("violations") != 0:
            failures.append(f"invariant violations in the restarted "
                            f"child: {stats.get('violations')!r} "
                            "(gate: 0)")
        if stats.get("auditFailed"):
            failures.append("the child's fail-fast auditor tripped")
        if (stats.get("parity") or {}).get("divergences"):
            failures.append("parity divergence: a cached executable gave "
                            "a wrong answer")
    except Exception as e:  # a dead bench must fail loudly, not silently
        failures.append(f"bench crashed: {type(e).__name__}: {e}")
        result.setdefault("invariant_violations", None)
    finally:
        churn_stop.set()
        for closer in (
                (lambda: sched.stop()) if sched is not None else None,
                (lambda: server.stop()) if server is not None else None):
            if closer is not None:
                try:
                    closer()
                except Exception:
                    pass
        shutil.rmtree(data_dir, ignore_errors=True)
        if outside_cache is not None:
            os.environ[CACHE_DIR_ENV] = outside_cache
    result["slo_failures"] = failures
    return result


if __name__ == "__main__":
    import json
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    _log = lambda *a: print(*a, file=sys.stderr)
    case = os.environ.get("BENCH_DISASTER_CASE", "apiserver")
    if case == "scheduler-kill":
        res = run_scheduler_kill(
            n_nodes=int(os.environ.get("BENCH_DISASTER_NODES", "16")),
            n_pods=int(os.environ.get("BENCH_DISASTER_PODS", "48")),
            bind_slo_s=float(os.environ.get(
                "BENCH_SCHED_KILL_BIND_SLO", "3")),
            log=_log)
    else:
        res = run_disaster_churn(
            n_hollow=int(os.environ.get("BENCH_DISASTER_NODES", "48")),
            n_pods=int(os.environ.get("BENCH_DISASTER_PODS", "96")),
            outage_s=float(os.environ.get("BENCH_DISASTER_OUTAGE_S", "16")),
            bind_slo_s=float(os.environ.get("BENCH_DISASTER_BIND_SLO",
                                            "10")),
            log=_log)
    print(json.dumps(res))
    if res.get("slo_failures") or res.get("invariant_violations"):
        sys.exit(1)
