"""Connected-path benchmark: SchedulerRunner against a SEPARATE-PROCESS
apiserver.

The raw gang numbers (scheduler_perf.py) measure the device program alone;
this measures the PRODUCT — informers watching the apiserver over HTTP, the
scheduling queue, the cache's incremental encode, the device-resident fused
drain, and bulk binding POSTs — in the reference's deployment shape: the
apiserver and the scheduler are separate processes (separate binaries
upstream), so API serving and watch fan-out do not share the scheduler's
interpreter. The measured window matches upstream scheduler_perf's
``createPods`` op: scheduler running and synced, clock starts at pod
creation, stops when the last binding is visible in the store.
"""

from __future__ import annotations

import contextlib
import multiprocessing as mp
import time


def _trace_window() -> None:
    """Arm the process-global tracer for a measured window."""
    from kubernetes_tpu.utils.tracing import TRACER
    TRACER.max_spans = 200_000  # keep long/timed-out windows untruncated
    TRACER.reset()


def _span_totals() -> dict:
    """Span name -> total ms since _trace_window()."""
    from kubernetes_tpu.utils.tracing import TRACER
    out: dict = {}
    for s in TRACER.spans():
        out[s.name] = round(out.get(s.name, 0.0) + s.duration_ms, 1)
    return out


def _counter_deltas(counter, base: dict) -> dict:
    """Labelled counter -> {label values joined: increase since ``base``}
    (the registry is process-global and earlier phases ran in this
    process, so every window reports deltas)."""
    out = {}
    for key, v in counter.items().items():
        dv = v - base.get(key, 0.0)
        if dv:
            out["".join(k for _, k in key)] = dv
    return out


def _compile_delta(since: dict, now: dict) -> dict:
    """Compile-meter movement between two snapshots (parallel/aot.py):
    realCompiles is genuine XLA work — backend-compile events minus
    persistent-cache loads."""
    from kubernetes_tpu.parallel.aot import CompileMeter
    out = {k: now[k] - since[k] for k in since}
    out["realCompiles"] = CompileMeter.real_compiles(since, now)
    return out


@contextlib.contextmanager
def captured_logs(logger_name: str, level: int, quiet: bool = False):
    """Yield a list that collects the logger's records at ``level`` and
    above while the block runs. ``quiet`` also lowers the logger to that
    level and keeps the records from its other handlers — for reading a
    library's DEBUG lines without printing them."""
    import logging
    records: list = []

    class _Collect(logging.Handler):
        def emit(self, record):
            records.append(record)

    logger = logging.getLogger(logger_name)
    handler = _Collect(level=level)
    was = (logger.level, logger.propagate)
    if quiet:
        logger.setLevel(level)
        logger.propagate = False
    logger.addHandler(handler)
    try:
        yield records
    finally:
        logger.removeHandler(handler)
        logger.level, logger.propagate = was


def _cache_miss_names(records: list) -> list:
    """Names of the programs jax compiled because the persistent cache
    had no entry for them, read off jax._src.compiler's DEBUG line — a
    diagnostic for a warm boot that was not (the gate is the meter)."""
    return [str(r.args[0]) for r in records
            if str(r.msg).startswith("PERSISTENT COMPILATION CACHE MISS")
            and r.args]


def device_block() -> dict:
    """The device this process ran on, as jax reports it."""
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def _device_residency(runner) -> dict:
    """Where the resident drain context really lives: platforms and
    devices under its arrays, the active mesh, and how ``allocatable``
    (a node-axis array) is split — a mesh that degraded to one device, or
    a split that fell back to replication, shows here and nowhere in the
    throughput figure."""
    import jax
    sch = runner.scheduler
    mesh = sch._mesh
    out = {"armed": sch._drain_ctx is not None,
           "mesh": (None if mesh is None else {
               "shape": [int(x) for x in mesh.devices.shape],
               "platforms": sorted({d.platform for d in mesh.devices.flat}),
               "devices": sorted(int(d.id) for d in mesh.devices.flat)})}
    ctx = sch._drain_ctx
    if ctx is not None:
        leaves = jax.tree_util.tree_leaves(ctx["ct"])
        devs = {d for leaf in leaves for d in leaf.devices()}
        alloc = ctx["ct"].allocatable
        out.update(
            platforms=sorted({d.platform for d in devs}),
            devices=sorted(int(d.id) for d in devs),
            allocatable_shape=[int(x) for x in alloc.shape],
            allocatable_shards=[
                {"device": int(sh.device.id),
                 "rows": [int(sh.index[0].start or 0),
                          int(alloc.shape[0] if sh.index[0].stop is None
                              else sh.index[0].stop)]}
                for sh in alloc.addressable_shards])
    return out


def _serve(conn) -> None:
    """Server process: in-memory store + HTTP apiserver until told to stop."""
    from kubernetes_tpu.store.apiserver import APIServer
    server = APIServer().start()
    conn.send(server.port)
    conn.recv()  # any message = stop
    server.stop()


def check_slo_gates(result: dict, gates: dict) -> list[str]:
    """HARD SLO verdicts for a bench case: throughput floors and latency
    ceilings from the case config. A MISSING or unparseable figure fails
    exactly like a regressed one — BENCH_r05's summary crash silently
    nulled every number for three rounds, and a gate that treats None as
    'no data, pass' would do it again. Returns failure strings (empty =
    all gates green)."""
    failures: list[str] = []
    for key, bound in (gates or {}).items():
        if key == "SchedulingThroughput":
            val, ok = result.get("SchedulingThroughput"), "floor"
        elif key == "p99AttemptLatencySeconds":
            val, ok = result.get("p99_attempt_latency_s"), "ceiling"
        else:
            failures.append(f"unknown SLO gate {key!r} (refusing to skip)")
            continue
        if not isinstance(val, (int, float)):
            failures.append(f"{key}: value missing/unparsed ({val!r}) — "
                            f"gate {bound} cannot pass silently")
        elif ok == "floor" and val < bound:
            failures.append(f"{key}: {val} below the {bound} floor")
        elif ok == "ceiling" and val > bound:
            failures.append(f"{key}: {val} above the {bound}s ceiling")
    return failures


# LOOP_ERRORS sites that mean a device program's answer was replaced by
# a fallback's (per-batch path, serial host scan, inline fetch, oracle)
DEVICE_ERROR_SITES = ("device_drain", "device_gang", "device_preempt",
                      "drain_resolve", "resolver", "resolver_wait",
                      "drain_ready", "warm_patch")


def check_served_on_device(result: dict) -> list[str]:
    """Did the DEVICE produce this run_connected result? The product keeps
    going when a device program fails (mesh -> single device -> numpy
    oracle), so a run carried by the host binds every pod and reports a
    throughput like any other. Failure strings from the counters the
    product keeps, a MISSING number failing like a bad one (empty = the
    answers came from the resident device program at the configured
    layout, and nothing refuted them)."""
    def num(v):
        return v if isinstance(v, (int, float)) else None

    failures: list[str] = []
    pods, bound = num(result.get("pods")), num(result.get("bound"))
    if pods is None or bound != pods:
        failures.append(f"{bound}/{pods} pods bound")
    if num(result.get("invariant_violations")) != 0:
        failures.append("auditor violations: "
                        f"{result.get('invariant_violations')!r}")
    errs = result.get("loop_errors")
    if not isinstance(errs, dict):
        failures.append("loop-error deltas missing")
    else:
        failures += [f"loop error at {site}: {errs[site]:g}"
                     for site in DEVICE_ERROR_SITES if errs.get(site)]
    res = result.get("resilience") or {}
    if num(res.get("degradedIndex")) != 0:
        failures.append(f"degraded mode {res.get('degradedMode')!r} "
                        f"(index {res.get('degradedIndex')!r})")
    if num(res.get("breakerTrips")) != 0:
        failures.append(f"breaker trips: {res.get('breakerTrips')!r} "
                        f"({res.get('breakerTripReasons')})")
    if num((result.get("ctx_stats") or {}).get("rebuilds")) != 0:
        failures.append("resident context rebuilt: "
                        f"{result.get('ctx_stats')}")
    attempts = result.get("schedule_attempts")
    if not isinstance(attempts, dict) or attempts.get("error"):
        failures.append(f"schedule attempts with result=error: {attempts}")
    per_drain = (num(result.get("batch_size")) or 0) * (
        num(result.get("drain_batches")) or 0)
    drains = num(result.get("drains_dispatched"))
    if not per_drain or pods is None or drains is None \
            or drains < -(-pods // per_drain):
        failures.append(f"{drains} drains dispatched for {pods} pods at "
                        f"{per_drain} a drain")
    par = result.get("parity")
    if par is not None:
        if num(par.get("divergences")) != 0:
            failures.append(f"parity divergences: {par.get('divergences')!r}"
                            f" ({par.get('lastDivergence')})")
        if num(par.get("pending")) != 0:
            failures.append(f"{par.get('pending')!r} parity verdicts never "
                            "landed")
        judged = num((par.get("samples") or {}).get("drain"))
        if par.get("every") == 1 and (judged is None or drains is None
                                      or judged < drains):
            failures.append(f"{judged} of {drains} drains parity-judged "
                            f"(skipped {par.get('skipped')})")
    want = (result.get("device") or {}).get("platform")
    rsd = result.get("residency") or {}
    if not rsd.get("armed") or want is None \
            or rsd.get("platforms") != [want]:
        failures.append(f"resident context not armed on {want!r}: {rsd}")
    shape = result.get("mesh_shape")
    if shape is not None:
        failures += _check_mesh_residency(rsd, tuple(shape), want)
    return failures


def _check_mesh_residency(rsd: dict, shape: tuple, platform) -> list[str]:
    """The configured mesh is live on that many devices of the platform,
    and a node-axis array is really split over the "nodes" axis: equal,
    disjoint row ranges on distinct devices — not the scheduler's
    single-device degrade, not _split_or_replicate's replica."""
    n_dev, n_split = shape[0] * shape[1], shape[1]
    mesh = rsd.get("mesh")
    if (not mesh or tuple(mesh.get("shape") or ()) != shape
            or mesh.get("platforms") != [platform]
            or len(set(mesh.get("devices") or ())) != n_dev):
        return [f"mesh {shape[0]}x{shape[1]} not live on {n_dev} "
                f"{platform} devices: {mesh}"]
    shards = rsd.get("allocatable_shards") or []
    n_rows = (rsd.get("allocatable_shape") or [0])[0]
    ranges = {tuple(sh["rows"]) for sh in shards}
    if (len({sh["device"] for sh in shards}) != n_dev
            or len(ranges) != n_split or n_rows % n_split
            or any(hi - lo != n_rows // n_split for lo, hi in ranges)):
        return [f"node axis ({n_rows} rows) not split {n_split} ways over "
                f"{n_dev} devices: {shards}"]
    return []


def _bench_auditor(runner, clean_client, interval_s: float = 2.0):
    """Fail-fast invariant auditor for a bench window (replaces the
    runner's production-cadence auditor BEFORE start): tight sweeps, a
    clean ground-truth client, raise-on-violation semantics."""
    from kubernetes_tpu.audit.auditor import InvariantAuditor
    return InvariantAuditor(
        client=clean_client, cache=runner.cache,
        scheduler=runner.scheduler, interval_s=interval_s, fail_fast=True,
        pre_sweep=runner.sweep_stale_nominations,
        post_sweep=runner.publish_status,
        relists=runner._total_relists)


def _audit_close(runner) -> dict:
    """Stop the bench auditor, run two settle sweeps (confirm-2 invariants
    need consecutive observations of end-state corruption), and return the
    block every audited bench case records. Never raises: the violations
    are already counted/bundled and the caller gates on the count."""
    from kubernetes_tpu.audit.auditor import InvariantViolationError
    auditor = runner.auditor
    auditor.stop()
    for _ in range(2):
        try:
            auditor.run_once()
        except InvariantViolationError:
            pass  # recorded + bundled; the count below fails the bench
    out = {"invariant_violations": auditor.total_violations,
           "audit": auditor.status()}
    sentinel = runner.scheduler.sentinel
    if sentinel is not None:
        # returns as soon as every verdict has landed; a full-size capture
        # takes seconds to judge, and ``pending`` > 0 in the stats is a
        # verdict that is MISSING, not one that passed
        sentinel.drain(timeout=300.0)
        out["parity"] = sentinel.stats()
    return out


def _watch_bound(url: str, ns: str, rv0: int, n_pods: int,
                 count, done, dead, ready) -> None:
    """Watcher process: count pods whose nodeName got set (one event per
    binding); its JSON decode burns its own interpreter, not the
    scheduler's."""
    from kubernetes_tpu.client.clientset import HTTPClient
    client = HTTPClient(url, timeout=30.0)
    seen: set = set()
    try:
        w = client.pods(ns).watch(since_rv=rv0)
        ready.set()  # stream established; the clock may start
        for ev in w:
            if (ev.object or {}).get("spec", {}).get("nodeName"):
                seen.add(ev.object["metadata"]["name"])
                count.value = len(seen)
                if len(seen) >= n_pods:
                    done.set()
                    return
    except Exception:
        import traceback
        traceback.print_exc()
    dead.set()


def _churn_loop(client, stop, period_s: float = 0.1, counter=None,
                hurry=None) -> None:
    """scheduler_perf's ``churn`` op analog: recycle nodes and short-lived
    pods (namespace ``churn``, excluded from the measured set) during the
    measured window. Exercises event-driven requeue
    (MoveAllToActiveOrBackoffQueue on node events), cache delta deletes,
    and the drain context's invalidate-and-rebuild path under load.
    ``hurry``: optional Event — once set, the loop drops to a 10ms cadence
    so a fixed op budget completes quickly after the measured drain."""
    import itertools
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    seq = itertools.count()
    live_nodes: list = []
    live_pods: list = []
    while not stop.is_set():
        i = next(seq)
        try:
            node = make_node(f"churn-n{i}").capacity(
                {"cpu": "2", "memory": "4Gi", "pods": "8"}).obj()
            client.nodes().create(node.to_dict())
            live_nodes.append(node.metadata.name)
            pod = make_pod(f"churn-p{i}", "churn").req({"cpu": "100m"}).obj()
            client.pods("churn").create(pod.to_dict())
            live_pods.append(pod.metadata.name)
            if len(live_nodes) > 3:
                client.nodes().delete(live_nodes.pop(0))
            if len(live_pods) > 3:
                client.pods("churn").delete(live_pods.pop(0))
            if counter is not None:
                counter["ops"] = counter.get("ops", 0) + 4
        except Exception:
            pass  # churn is background noise; the bench owns correctness
        stop.wait(period_s if hurry is None or not hurry.is_set()
                  else min(period_s, 0.01))


def run_connected(n_pods: int = 2000, n_nodes: int = 1000,
                  batch_size: int = 512, drain_batches: int = 2,
                  timeout: float = 300.0, churn: bool = False,
                  churn_period_s: float = 0.1, min_churn_ops: int = 500,
                  pipeline_depth: int | None = None,
                  fault_schedule=None,
                  seed: int = 0,
                  cfg_extra: dict | None = None,
                  log=lambda *a: None) -> dict:
    """One served-path window. ``seed`` makes the cluster and the pods;
    ``cfg_extra`` adds SchedulerConfiguration fields (mesh_shape,
    parity_sample_every, ...); ``fault_schedule`` is a ready FaultSchedule
    to run under. Whatever the mode, the result carries the resilience
    block, the loop-error and attempt deltas over the run (warm ladder
    included) and where the resident context lives — a run the breaker carried on the host binds
    every pod too, and only these say so."""
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.metrics.registry import ATTEMPT_DURATION
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.utils.tracing import FLIGHT
    from benchmarks.workloads import mixed_heterogeneous

    ctx = mp.get_context("spawn")  # never fork a live TPU client
    parent, child = ctx.Pipe()
    server = ctx.Process(target=_serve, args=(child,), daemon=True)
    server.start()
    port = parent.recv()
    url = f"http://127.0.0.1:{port}"
    schedule = device_chaos = None
    try:
        seed_client = HTTPClient(url, timeout=120.0)
        nodes, pods = mixed_heterogeneous(pods=n_pods, nodes=n_nodes,
                                          seed=seed)
        t0 = time.time()
        seed_client.nodes().create_many([n.to_dict() for n in nodes])
        log(f"  seeded {n_nodes} nodes in {time.time()-t0:.1f}s")

        cfg_kw = dict(batch_size=batch_size,
                      max_drain_batches=drain_batches)
        if pipeline_depth is not None:
            # clamp like the scheduler does, so the reported depth is the
            # depth that actually ran (depth 0 would silently run as 1)
            cfg_kw["pipeline_depth"] = max(1, int(pipeline_depth))
        sched_client = HTTPClient(url)
        if fault_schedule is not None:
            # ChaosChurn: the SCHEDULER's transport is chaos-wrapped (the
            # harness's own seed/verify clients stay clean — the bench
            # owns ground truth), device + thread faults install after
            # warmup so the measured window eats them, and the breaker
            # cooldown shrinks so half-open recovery happens inside the
            # window. The seed is logged: any failure replays from it.
            from kubernetes_tpu.chaos import ChaosClient
            schedule = fault_schedule
            log(f"  chaos schedule armed (seed {schedule.seed}; "
                f"KTPU_CHAOS_SEED replays it)")
            sched_client = ChaosClient(sched_client, schedule)
            cfg_kw["breaker_cooldown_s"] = 5.0
            # chaos runs sample the parity sentinel densely: the device
            # fault burst is exactly when a wrong-answer regression would
            # hide behind the breaker's exception-only view
            cfg_kw["parity_sample_every"] = 4
        cfg_kw.update(cfg_extra or {})
        runner = SchedulerRunner(sched_client,
                                 SchedulerConfiguration(**cfg_kw))
        sentinel = runner.scheduler.sentinel
        if sentinel is not None and sentinel.every == 1:
            # every drain judged means EVERY drain: the sentinel sheds
            # samples past a backlog of 8 while its checker is busy, and a
            # full-size capture takes longer to judge than a drain to run
            sentinel.max_backlog = max(
                sentinel.max_backlog,
                -(-n_pods // batch_size) + 8)
        from kubernetes_tpu.metrics.registry import (BIND_RETRIES,
                                                     LOOP_ERRORS,
                                                     PIPELINE_DEPTH,
                                                     SCHEDULE_ATTEMPTS)
        # the registry is process-global and earlier bench phases ran in
        # this process: snapshot now, diff at report time, so the result
        # attributes only THIS run's errors/retries (warm ladder included)
        run_base = {"bind_retries": BIND_RETRIES.get(),
                    "loop_errors": LOOP_ERRORS.items(),
                    "attempts": SCHEDULE_ATTEMPTS.items(),
                    "drains": PIPELINE_DEPTH.count()}
        # fail-fast invariant audit over the whole measured run: sweeps
        # ride a CLEAN client (the bench owns ground truth; the chaos
        # wrapper stays on the scheduler's transport only) and any
        # confirmed violation is recorded + repro-bundled, then reported
        # as invariant_violations in this case's JSON — chip_smoke.py
        # exits non-zero on it (the loud-failure lesson, applied to
        # correctness)
        runner.auditor = _bench_auditor(runner, HTTPClient(url))
        # informers first (nodes sync into the scheduler cache); the loop
        # starts after pod creation so the first pop drains a deep backlog
        runner.start(start_loop=False)
        from kubernetes_tpu.parallel.aot import compile_meter
        meter = compile_meter()
        m_boot, t_warm = meter.snapshot(), time.time()
        _warm_jit(runner, pods, batch_size, n_pods, log)
        m_warm, warm_s = meter.snapshot(), round(time.time() - t_warm, 2)
        if schedule is not None:
            from kubernetes_tpu.chaos import (DeviceChaos, ThreadChaos,
                                              hooks)
            device_chaos = DeviceChaos(schedule).install()
            hooks.install(ThreadChaos(schedule))

        _, rv0 = seed_client.pods("default").list_rv()
        count = ctx.Value("i", 0)
        all_bound, watch_dead, ready = ctx.Event(), ctx.Event(), ctx.Event()
        watcher = ctx.Process(target=_watch_bound,
                              args=(url, "default", rv0, n_pods,
                                    count, all_bound, watch_dead, ready),
                              daemon=True)
        watcher.start()
        ready.wait(30.0)  # spawn + import + stream setup is seconds

        churn_stop = churn_hurry = None
        churn_stats: dict = {}
        if churn:
            import threading
            churn_stop = threading.Event()
            churn_hurry = threading.Event()
            threading.Thread(target=_churn_loop,
                             args=(HTTPClient(url), churn_stop),
                             kwargs={"counter": churn_stats,
                                     "period_s": churn_period_s,
                                     "hurry": churn_hurry},
                             daemon=True).start()

        _trace_window()  # spans from here on belong to the measured window
        # the registry is process-global: an earlier bench phase's attempts
        # (e.g. the churn workload) must not pollute this window's p99
        ATTEMPT_DURATION.reset()
        from kubernetes_tpu.metrics.registry import (E2E_SCHEDULING,
                                                     UNSCHEDULABLE_REASONS)
        FLIGHT.reset()
        E2E_SCHEDULING.reset()
        reasons_base = UNSCHEDULABLE_REASONS.items()
        t_start = time.time()
        by_ns: dict = {}
        for p in pods:
            by_ns.setdefault(p.metadata.namespace, []).append(p.to_dict())
        # concurrent bulk creates (upstream scheduler_perf's createPods op
        # runs with client-side concurrency): chunks land on separate
        # apiserver handler threads, overlapping decode/store work
        from concurrent.futures import ThreadPoolExecutor
        CREATE_CHUNK = 2500
        jobs = [(ns, objs[i:i + CREATE_CHUNK])
                for ns, objs in by_ns.items()
                for i in range(0, len(objs), CREATE_CHUNK)]

        def create(job):
            ns, objs = job
            # seed_client is thread-safe: connections live in
            # threading.local, so each pool thread gets its own socket
            seed_client.pods(ns).create_many(objs)
        with ThreadPoolExecutor(max_workers=min(4, len(jobs))) as pool:
            list(pool.map(create, jobs))
        t_created = time.time()
        runner.start_loop()
        deadline = t_start + timeout
        completed = False
        milestones: dict = {}  # fraction bound -> seconds since t_start
        while time.time() < deadline:
            n = count.value
            for frac in (0.25, 0.5, 0.75):
                if n >= n_pods * frac and frac not in milestones:
                    milestones[frac] = round(time.time() - t_start, 2)
            if all_bound.wait(timeout=0.02):
                completed = True
                break
            if watch_dead.is_set():
                # watch failed: poll the store for the truth instead of
                # silently waiting out the timeout with a dead detector
                n = sum(1 for p in seed_client.pods("default").list()
                        if p["spec"].get("nodeName"))
                count.value = n
                if n >= n_pods:
                    completed = True
                    break
                time.sleep(0.2)
        dt = time.time() - t_start
        bound = count.value
        if not completed:  # timed out: relist for the truth
            bound = sum(1 for p in seed_client.pods("default").list()
                        if p["spec"].get("nodeName"))
        # fractions crossed inside the final wait (or a sub-interval run)
        for frac in (0.25, 0.5, 0.75):
            if bound >= n_pods * frac and frac not in milestones:
                milestones[frac] = round(dt, 2)
        log(f"  created {n_pods} pods in {t_created-t_start:.1f}s; "
            f"all bound at +{dt:.1f}s")
        # Snapshot the MEASURED window's metrics BEFORE the churn budget
        # phase below: the hurry-phase keeps the live scheduler processing
        # small fast churn batches, which would otherwise skew the reported
        # p99/p50/span totals the same way an earlier phase would.
        # p99 attempt latency (scheduled results) from the live histogram —
        # bucket upper bound, like Prometheus histogram_quantile
        p99 = ATTEMPT_DURATION.percentile(0.99, {"result": "scheduled"})
        p50 = ATTEMPT_DURATION.percentile(0.50, {"result": "scheduled"})
        # where the window went: scheduler-side span totals (ms) + the bind
        # progress curve, so a BENCH file diagnoses its own bottleneck
        span_ms = _span_totals()
        attempt_buckets = [
            (b, c) for b, c in ATTEMPT_DURATION.bucket_counts(
                {"result": "scheduled"}) if c]
        ctx_stats = dict(runner.scheduler.ctx_stats)
        encode_cache = runner.cache.encode_cache_stats()
        residency = _device_residency(runner)
        # set-up vs window: the ladder's compiles (or cache loads) are
        # set-up; a steady window should show none of either
        compile_block = {"warm_s": warm_s,
                         "warm": _compile_delta(m_boot, m_warm),
                         "window": _compile_delta(m_warm, meter.snapshot())}
        # decision-provenance + flight-recorder attribution for this
        # window: reason breakdown, explainer thread totals (its spans are
        # explain/* in span_ms — all off the drain cycle), per-pod
        # timeline coverage, and the derived end-to-end SLI
        explain_block = None
        ex = runner.scheduler.explainer
        if ex is not None:
            ex.drain(5.0)
            explain_block = ex.stats()
            # re-snapshot AFTER the drain: a capture still queued at the
            # span_ms snapshot finishes its explain/* spans inside the
            # drain, and the cost attribution must include them
            explain_block["span_ms"] = {
                k: v for k, v in _span_totals().items()
                if k.startswith("explain/")}
        unsched_reasons = _counter_deltas(UNSCHEDULABLE_REASONS,
                                          reasons_base)
        flight_block = FLIGHT.stats()
        e2e_block = {"count": E2E_SCHEDULING.count(),
                     "p50_s": E2E_SCHEDULING.percentile(0.50),
                     "p99_s": E2E_SCHEDULING.percentile(0.99)}
        case_name = ("ChaosChurn" if schedule is not None
                     else "ConnectedChurn" if churn
                     else "ConnectedScheduler")
        if churn_stop is not None:
            # fixed churn-op budget DECOUPLED from drain duration: a fast
            # drain must not mean the churn path went unexercised (r05: the
            # 2k-pod window shrank to 1.2s and applied only 36 ops). Keep
            # churning at a hurried cadence against the LIVE scheduler
            # until the budget lands, then tear down.
            churn_hurry.set()
            budget_deadline = time.time() + 60.0
            while (churn_stats.get("ops", 0) < min_churn_ops
                   and time.time() < budget_deadline):
                time.sleep(0.05)
            churn_stop.set()
        if schedule is not None:
            from kubernetes_tpu.chaos import hooks
            hooks.uninstall()
            if device_chaos is not None:
                device_chaos.uninstall()
                device_chaos = None
        audit_block = _audit_close(runner)
        runner.stop()
        out = {
            "case": case_name,
            "workload": f"{n_pods}x{n_nodes}",
            "SchedulingThroughput": round(bound / dt, 1) if dt > 0 else 0.0,
            "bound": bound, "pods": n_pods, "nodes": n_nodes,
            "measure_s": round(dt, 2),
            "watch_degraded": watch_dead.is_set(),
            "p99_attempt_latency_s": p99,
            "p50_attempt_latency_s": p50,
            "create_s": round(t_created - t_start, 2),
            "bound_frac_s": milestones,
            "span_ms": span_ms,
            "seed": seed,
            "batch_size": batch_size, "drain_batches": drain_batches,
            "mesh_shape": (list(runner.cfg.mesh_shape)
                           if runner.cfg.mesh_shape else None),
            "device": device_block(),
            # did the DEVICE do the work? The same resilience aggregation
            # ktpu status shows, plus this run's deltas of the loop-error
            # sites, attempt results and dispatched drains
            "resilience": runner._resilience_status(),
            "loop_errors": _counter_deltas(LOOP_ERRORS,
                                           run_base["loop_errors"]),
            "schedule_attempts": _counter_deltas(SCHEDULE_ATTEMPTS,
                                                 run_base["attempts"]),
            "bind_retries": BIND_RETRIES.get() - run_base["bind_retries"],
            "drains_dispatched": PIPELINE_DEPTH.count() - run_base["drains"],
            "residency": residency,
            "compile": compile_block,
        }
        if churn:
            out["churn_api_ops"] = churn_stats.get("ops", 0)
        if schedule is not None:
            # the gate's inputs: lost = pods the run failed to bind (the
            # caller exits non-zero on any) and recovery spans per fault
            # class; resilience/loop_errors/bind_retries stay here too for
            # readers of the chaos block
            out["chaos"] = {
                "seed": schedule.seed,
                "lost": n_pods - bound,
                "recovery": schedule.report(),
                "resilience": out["resilience"],
                "bind_retries": out["bind_retries"],
                "loop_errors": out["loop_errors"],
            }
        # pipeline + incremental-encode attribution (measured-window
        # snapshot, like p99/spans): depth knob in effect, and how many pod
        # rows the hot path served from the informer-time compile cache
        out["ctx_stats"] = ctx_stats
        out["pipeline_depth"] = runner.cfg.pipeline_depth
        out["encode_cache"] = encode_cache
        out["attempt_buckets"] = attempt_buckets
        out["unschedulable_reasons"] = unsched_reasons
        out["explain"] = explain_block
        out["flight"] = flight_block
        out["e2e"] = e2e_block
        out.update(audit_block)
        return out
    finally:
        if schedule is not None:  # crash path: never leak installed chaos
            from kubernetes_tpu.chaos import hooks as _hooks
            _hooks.uninstall()
            if device_chaos is not None:
                device_chaos.uninstall()
        try:
            parent.send("stop")
        except Exception:
            pass
        server.join(timeout=5.0)
        if server.is_alive():
            server.terminate()


def run_warm_ladder(n_pods: int = 2000, n_nodes: int = 1000,
                    batch_size: int = 512, drain_batches: int = 2,
                    seed: int = 0, cfg_extra: dict | None = None,
                    log=lambda *a: None) -> dict:
    """Boot a scheduler against a freshly seeded cluster identical to
    run_connected's (same seed, same sizes, same config) and run ONLY the
    warm ladder. In a process started after a run_connected with the same
    arguments, every program should load from the persistent compile
    cache: the result carries the compile meter's movement, the wall
    seconds as set-up time, and the names of programs that missed."""
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.parallel.aot import compile_meter
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from benchmarks.workloads import mixed_heterogeneous

    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe()
    server = ctx.Process(target=_serve, args=(child,), daemon=True)
    server.start()
    url = f"http://127.0.0.1:{parent.recv()}"
    runner = None
    try:
        nodes, pods = mixed_heterogeneous(pods=n_pods, nodes=n_nodes,
                                          seed=seed)
        HTTPClient(url, timeout=120.0).nodes().create_many(
            [n.to_dict() for n in nodes])
        cfg_kw = dict(batch_size=batch_size,
                      max_drain_batches=drain_batches)
        cfg_kw.update(cfg_extra or {})
        runner = SchedulerRunner(HTTPClient(url),
                                 SchedulerConfiguration(**cfg_kw))
        runner.start(start_loop=False)
        meter = compile_meter()
        base, t0 = meter.snapshot(), time.time()
        import logging
        with captured_logs("jax._src.compiler", logging.DEBUG,
                           quiet=True) as jax_logs:
            _warm_jit(runner, pods, batch_size, n_pods, log)
        return {"case": "WarmLadder", "workload": f"{n_pods}x{n_nodes}",
                "seed": seed, "device": device_block(),
                "warm_s": round(time.time() - t0, 2),
                "compile": _compile_delta(base, meter.snapshot()),
                "missed": _cache_miss_names(jax_logs),
                "aotCache": runner._aot_cache_status(),
                "residency": _device_residency(runner)}
    finally:
        if runner is not None:
            runner.stop()
        try:
            parent.send("stop")
        except Exception:
            pass
        server.join(timeout=5.0)
        if server.is_alive():
            server.terminate()


def drain_parity_check(mesh_shape: tuple[int, int], n_nodes: int = 1024,
                       P: int = 128, B: int = 2, seed: int = 0) -> dict:
    """Deterministic mesh acceptance gate: the FULL fused drain over the
    bench workload, sharded vs unsharded, must produce bit-identical
    placements and fold arithmetic (same check as __graft_entry__'s
    multichip dry-run, at the live path's shapes). chip_smoke.py exits
    non-zero when this reports ok=False."""
    import jax
    import numpy as np
    from benchmarks.workloads import mixed_heterogeneous
    from kubernetes_tpu.encode.snapshot import SnapshotEncoder
    from kubernetes_tpu.models.gang import (drain_step, extend_cluster_drain,
                                            unify_batches)
    from kubernetes_tpu.parallel.mesh import mesh_from_shape, shard_drain

    n_pods = P * B
    nodes, pods = mixed_heterogeneous(pods=n_pods, nodes=n_nodes,
                                      seed=seed)
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, [], pending_pods=pods)
    chunks = [pods[i:i + P] for i in range(0, n_pods, P)]
    pbs = unify_batches([enc.encode_pods(c, meta, min_p=P) for c in chunks])
    ct_all, e0 = extend_cluster_drain(ct, pbs)
    pb_stack = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *pbs)
    kw = dict(e0=e0, seed=0, fit_strategy="LeastAllocated",
              topo_keys=meta.topo_keys, weights=(), enabled_filters=(),
              max_rounds=64)
    a_u, _, _, fill_u = drain_step(ct_all, pb_stack, 0, **kw)
    a_u, fill_u = jax.device_get((a_u, fill_u))
    mesh = mesh_from_shape(mesh_shape)
    ct_all2, _ = extend_cluster_drain(ct, pbs)
    with mesh:
        # mesh= pins the output shardings to the input shardings (the
        # donate-through contract) — the exact program the live leg runs
        ct_s, pb_s = shard_drain(mesh, ct_all2, pb_stack)
        a_s, _, _, fill_s = drain_step(ct_s, pb_s, 0, mesh=mesh, **kw)
        a_s, fill_s = jax.device_get((a_s, fill_s))
    a_u, a_s = np.asarray(a_u), np.asarray(a_s)
    mism = int((a_u != a_s).sum())
    return {"ok": bool(mism == 0 and int(fill_u) == int(fill_s)
                       and int(fill_u) > 0),
            "mismatches": mism, "placed": int(fill_u),
            "pods": n_pods, "nodes": n_nodes,
            "mesh": f"{mesh_shape[0]}x{mesh_shape[1]}"}


def run_connected_preemption(n_nodes: int = 5000, n_high: int = 128,
                             pods_per_node: int = 2, timeout: float = 300.0,
                             log=lambda *a: None) -> dict:
    """Mixed schedule+preempt through the PRODUCT: a saturated cluster
    behind the live apiserver, a wave of high-priority pods arrives, and
    the connected scheduler's failure path must wave-preempt (evict via the
    API), nominate, and re-bind — measured pod-creation to last binding
    visible, like the plain connected run. Exercises
    scheduler._handle_failures -> _default_preempt_wave -> runner._evict
    end to end (VERDICT r3: preemption had never run through the product)."""
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe()
    server = ctx.Process(target=_serve, args=(child,), daemon=True)
    server.start()
    port = parent.recv()
    url = f"http://127.0.0.1:{port}"
    try:
        seed_client = HTTPClient(url, timeout=120.0)
        t0 = time.time()
        seed_client.nodes().create_many(
            [make_node(f"n{i}").capacity(
                {"cpu": "8", "memory": "32Gi", "pods": "32"}).obj().to_dict()
             for i in range(n_nodes)])
        low = []
        for i in range(n_nodes):
            for j in range(pods_per_node):
                low.append(make_pod(f"low-{i}-{j}", "default")
                           .req({"cpu": "4", "memory": "4Gi"})
                           .priority(1 + (i + j) % 5).node(f"n{i}").obj()
                           .to_dict())
        seed_client.pods("default").create_many(low)
        log(f"  seeded {n_nodes} nodes + {len(low)} bound low-prio pods "
            f"in {time.time()-t0:.1f}s")

        runner = SchedulerRunner(
            HTTPClient(url), SchedulerConfiguration(batch_size=256,
                                                    max_drain_batches=1))
        runner.start(wait_sync=60.0, start_loop=False)
        _warm_preempt(runner, n_high, log)

        _trace_window()
        high = [make_pod(f"hi-{k}", "preempt")
                .req({"cpu": "6", "memory": "8Gi"}).priority(100).obj()
                for k in range(n_high)]
        _, rv0 = seed_client.pods("preempt").list_rv()
        count = ctx.Value("i", 0)
        all_bound, watch_dead, ready = ctx.Event(), ctx.Event(), ctx.Event()
        watcher = ctx.Process(target=_watch_bound,
                              args=(url, "preempt", rv0, n_high,
                                    count, all_bound, watch_dead, ready),
                              daemon=True)
        watcher.start()
        ready.wait(30.0)

        t_start = time.time()
        seed_client.pods("preempt").create_many([p.to_dict() for p in high])
        runner.start_loop()
        deadline = t_start + timeout
        completed = False
        while time.time() < deadline:
            if all_bound.wait(timeout=0.05):
                completed = True
                break
            if watch_dead.is_set():
                n = sum(1 for p in seed_client.pods("preempt").list()
                        if p["spec"].get("nodeName"))
                count.value = n
                if n >= n_high:
                    completed = True
                    break
                time.sleep(0.2)
        dt = time.time() - t_start
        bound = count.value
        if not completed:
            bound = sum(1 for p in seed_client.pods("preempt").list()
                        if p["spec"].get("nodeName"))
        log(f"  {bound}/{n_high} preemptors bound at +{dt:.1f}s")
        runner.stop()
        span_ms = _span_totals()
        remaining = len(seed_client.pods("default").list())
        return {
            "case": "ConnectedPreemption",
            "workload": f"{n_high}x{n_nodes}",
            "PreemptionThroughput": round(bound / dt, 1) if dt > 0 else 0.0,
            "resolved": bound, "preemptors": n_high, "nodes": n_nodes,
            "measure_s": round(dt, 2),
            "victims_evicted": len(low) - remaining,
            "watch_degraded": watch_dead.is_set(),
            "span_ms": span_ms,
        }
    finally:
        try:
            parent.send("stop")
        except Exception:
            pass
        server.join(timeout=5.0)
        if server.is_alive():
            server.terminate()


def _warm_preempt(runner, n_high: int, log) -> None:
    """Compile the preemption-path device programs BEFORE the measured
    window, mutating nothing: the gang program at the failure batch's
    shapes, the [Q,N] static-mask filters, and the Q-length wave scan
    (scan length is structural, so Q must match n_high). A long-lived
    scheduler amortizes these once; the bench should measure preemption
    resolution, not XLA compilation. A program that fails to compile or
    run here raises: the window would otherwise measure its fallback."""
    import time as _time
    t0 = _time.time()
    from kubernetes_tpu.models.gang import gang_schedule
    from kubernetes_tpu.ops.preemption import dry_run_wave
    from kubernetes_tpu.sched import preemption as pmod
    from kubernetes_tpu.sched.scheduler import DRAIN_NOM_BUCKET
    from kubernetes_tpu.testing.wrappers import make_pod
    cache = runner.cache
    profile = runner.cfg.profiles[0]
    warm = [make_pod(f"warm-{k}", "warmup")
            .req({"cpu": "6", "memory": "8Gi"}).priority(100).obj()
            for k in range(n_high)]
    nodes, ct, meta = cache.snapshot(pending_pods=warm)
    bound = cache.bound_pods()
    # the runtime group path pins batch width to cfg.batch_size and the
    # nominee overlay to DRAIN_NOM_BUCKET — compile exactly those
    # shapes, with and without reservations (first cycle has none)
    pb = cache.encode_pods(warm, meta, min_p=runner.cfg.batch_size)
    gang_schedule(ct, pb, seed=runner.cfg.seed,
                  fit_strategy=profile.fit_strategy,
                  topo_keys=meta.topo_keys, weights=profile.weights(),
                  enabled_filters=profile.enabled_filters)
    nom = [(meta.node_names[0], 100, warm[0])]
    ct_nom = cache.overlay_nominated(ct, meta, nom, min_m=DRAIN_NOM_BUCKET)
    gang_schedule(ct_nom, pb, seed=runner.cfg.seed,
                  fit_strategy=profile.fit_strategy,
                  topo_keys=meta.topo_keys, weights=profile.weights(),
                  enabled_filters=profile.enabled_filters)
    # same bucket pinning as the scheduler's wave path, so every wave
    # of the storm hits the programs compiled here
    masks = pmod.tensor_static_masks(
        nodes, warm, ct=ct, meta=meta, encode_pods=cache.encode_pods,
        min_p=pmod.WAVE_BUCKET)
    dry_run_wave(nodes, bound, warm, [], static_masks=masks,
                 min_q=pmod.WAVE_BUCKET)
    log(f"  preempt warmup {_time.time()-t0:.1f}s")


def _warm_jit(runner, pods, batch_size, n_pods, log) -> None:
    """Compile the fused drain and arm the device-resident cluster context
    at the exact shapes the runner's pops will use, against the runner's OWN
    cache — so the measured window is pure steady state (a long-lived
    scheduler amortizes this once per shape bucket, as in scheduler_perf).
    Raises when the context does not arm."""
    t0 = time.time()
    armed = runner.scheduler.warm_drain(
        pods, slot_headroom=n_pods
        + batch_size * runner.cfg.max_drain_batches)
    log(f"  jit warmup {time.time()-t0:.1f}s (ctx armed: {armed})")
    if not armed:
        # an unarmed context means the window compiles and stages inside
        # itself: not a slower run of the same thing, a different thing
        raise RuntimeError("warm_drain did not arm the resident drain "
                           "context; refusing to measure a cold window")


if __name__ == "__main__":
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    _pipe = os.environ.get("BENCH_CONNECTED_PIPELINE")
    res = run_connected(
        n_pods=int(os.environ.get("BENCH_CONNECTED_PODS", "2000")),
        n_nodes=int(os.environ.get("BENCH_CONNECTED_NODES", "1000")),
        batch_size=int(os.environ.get("BENCH_CONNECTED_BATCH", "512")),
        drain_batches=int(os.environ.get("BENCH_CONNECTED_DRAIN", "2")),
        pipeline_depth=int(_pipe) if _pipe else None,
        log=lambda *a: print(*a, file=sys.stderr))
    print(json.dumps(res))
