"""ScenarioReplay bench case: the cluster time machine against the real
connected stack.

Resolves a trace (``builtin:<name>`` from the generator catalog, or a
``.trace.jsonl`` path — committed fixture, WAL capture, or audit-bundle
conversion), seeds its node fleet into a separate-process apiserver,
arms the manifest's chaos schedule if it carries one, and replays the
events through the time-warped driver while the fail-fast invariant
auditor sweeps the whole window.

Hard gates (reported as ``slo_failures``; bench.py exits non-zero):

* every trace-resident pod bound (lost pods fail, like ChaosChurn)
* per-phase p99 attempt latency PRESENT for every phase that left
  resident pods — a missing number fails exactly like a regressed one
* determinism: two independent resolutions of the same spec+seed plan
  the same dispatch order, and the live run dispatched exactly that plan
* the manifest's own sloGates (check_slo_gates vocabulary)
* 0 confirmed invariant violations (via the shared audit roll-up)
"""

from __future__ import annotations

import multiprocessing as mp
import time


def _resolve(spec: str, seed: int = 0):
    """``builtin:<name>`` -> generator catalog; anything else is a path."""
    from kubernetes_tpu.scenario import Trace, builtin_trace
    if spec.startswith("builtin:"):
        return builtin_trace(spec[len("builtin:"):], seed=seed)
    return Trace.load(spec)


def run_scenario_replay(spec: str = "builtin:smoke", speed: float = 4.0,
                        seed: int = 0, timeout: float = 180.0,
                        batch_size: int = 64,
                        log=lambda *a: None) -> dict:
    from benchmarks.connected import (_audit_close, _bench_auditor,
                                      _serve, check_slo_gates)
    from kubernetes_tpu.api.types import Pod
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.metrics.registry import ATTEMPT_DURATION
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.scenario import ScenarioDriver

    trace = _resolve(spec, seed=seed)
    # determinism gate, half 1: a SECOND independent resolution of the
    # same spec+seed must plan the identical dispatch order (generators
    # are pure; a file is just bytes)
    plan = ScenarioDriver(None, trace, publish=False).plan()
    plan2 = ScenarioDriver(None, _resolve(spec, seed=seed),
                           publish=False).plan()
    resident = trace.resident_pods()
    log(f"  trace {trace.manifest.name!r}: {len(trace)} events, "
        f"{len(resident)} resident pods, "
        f"{trace.duration_s():.1f}s at speed {speed}")

    ctx = mp.get_context("spawn")  # same rule as run_connected
    parent, child = ctx.Pipe()
    server = ctx.Process(target=_serve, args=(child,), daemon=True)
    server.start()
    port = parent.recv()
    url = f"http://127.0.0.1:{port}"
    schedule = device_chaos = None
    try:
        seed_client = HTTPClient(url, timeout=120.0)
        fleet = trace.fleet_nodes()
        if fleet:
            seed_client.nodes().create_many(fleet)
            log(f"  seeded {len(fleet)} fleet nodes")

        cfg_kw = dict(batch_size=batch_size, max_drain_batches=2)
        sched_client = HTTPClient(url)
        chaos_cfg = trace.manifest.chaos
        if chaos_cfg:
            # the recorded incident's fault schedule rides the manifest:
            # the SCHEDULER's transport is chaos-wrapped, the harness's
            # own clients stay clean (the bench owns ground truth)
            from kubernetes_tpu.chaos import ChaosClient, FaultSchedule
            schedule = FaultSchedule.generate(
                int(chaos_cfg.get("seed", 0)),
                profile=chaos_cfg.get("profile", "churn"))
            log(f"  chaos schedule armed (seed {schedule.seed})")
            sched_client = ChaosClient(sched_client, schedule)
            cfg_kw["breaker_cooldown_s"] = 5.0
            cfg_kw["parity_sample_every"] = 4
        runner = SchedulerRunner(sched_client,
                                 SchedulerConfiguration(**cfg_kw))
        runner.auditor = _bench_auditor(runner, HTTPClient(url))
        runner.start(start_loop=False)

        # warm the fused drain at the replay's shapes so the window is
        # steady state (a trace pod that eats a compile would post a
        # multi-second "attempt latency" that is really XLA's)
        warm_pods = []
        for ev in resident.values():
            try:
                warm_pods.append(Pod.from_dict(trace.materialize(ev)))
            except Exception:
                break  # recorded objs may predate the model's schema
        jit_warmed = False
        if len(warm_pods) == len(resident):
            t0 = time.time()
            jit_warmed = runner.scheduler.warm_drain(
                warm_pods, slot_headroom=len(warm_pods)
                + batch_size * runner.cfg.max_drain_batches)
            log(f"  jit warmup {time.time()-t0:.1f}s "
                f"(ctx armed: {jit_warmed})")

        if schedule is not None:
            from kubernetes_tpu.chaos import (DeviceChaos, ThreadChaos,
                                              hooks)
            device_chaos = DeviceChaos(schedule).install()
            hooks.install(ThreadChaos(schedule))

        runner.start_loop()
        # process-global registry: earlier bench phases must not pollute
        # this window's scheduler-side p99
        ATTEMPT_DURATION.reset()

        driver = ScenarioDriver(HTTPClient(url), trace, speed=speed,
                                bind_timeout_s=timeout, log=log)
        replay = driver.run()
        log(f"  replay: {replay['bound']}/{replay['resident']} bound "
            f"in {replay['wall_s']}s "
            f"(skew max {replay['skew']['max_s']}s)")

        p99 = ATTEMPT_DURATION.percentile(0.99, {"result": "scheduled"})
        p50 = ATTEMPT_DURATION.percentile(0.50, {"result": "scheduled"})

        if schedule is not None:
            from kubernetes_tpu.chaos import hooks
            hooks.uninstall()
            if device_chaos is not None:
                device_chaos.uninstall()
                device_chaos = None
        audit_block = _audit_close(runner)
        runner.stop()

        deterministic = (plan == plan2
                         and replay["dispatch_order"] == plan)
        wall = replay["wall_s"] or 1e-9
        out = {
            "case": "ScenarioReplay",
            "spec": spec,
            "trace": replay["trace"],
            "seed": replay["seed"],
            "speed": speed,
            "events_total": replay["events_total"],
            "dispatched": replay["dispatched"],
            "dispatch_error_count": replay["error_count"],
            "dispatch_errors": replay["errors"][:10],
            "resident": replay["resident"],
            "bound": replay["bound"],
            "lost": replay["resident"] - replay["bound"],
            "completed": replay["completed"],
            "dispatch_s": replay["dispatch_s"],
            "wall_s": replay["wall_s"],
            "SchedulingThroughput": round(replay["bound"] / wall, 1),
            "skew": replay["skew"],
            "phases": replay["phases"],
            "p99_attempt_latency_s": p99,
            "p50_attempt_latency_s": p50,
            "deterministic": deterministic,
            "jit_warmed": jit_warmed,
        }
        if schedule is not None:
            out["chaos"] = {"seed": schedule.seed,
                            "recovery": schedule.report()}
        out.update(audit_block)

        failures: list[str] = []
        if out["lost"]:
            failures.append(f"{out['lost']} of {out['resident']} "
                            "trace-resident pods never bound")
        for ph, st in sorted(replay["phases"].items()):
            if st["pods"] and not isinstance(
                    st.get("p99_attempt_latency_s"), (int, float)):
                failures.append(
                    f"phase {ph!r}: p99 attempt latency missing "
                    f"({st['pods']} pods) — gate cannot pass silently")
        if not deterministic:
            failures.append("replay is not deterministic: dispatch "
                            "order diverged from the plan (or two "
                            "resolutions of the spec disagree)")
        failures.extend(check_slo_gates(out, trace.manifest.slo_gates))
        out["slo_failures"] = failures
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


if __name__ == "__main__":
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    spec = os.environ.get("BENCH_SCENARIO", "builtin:smoke")
    res = run_scenario_replay(
        spec="builtin:smoke" if spec in ("", "1") else spec,
        speed=float(os.environ.get("BENCH_SCENARIO_SPEED", "4")),
        seed=int(os.environ.get("BENCH_SCENARIO_SEED", "0")),
        log=lambda *a: print(*a, file=sys.stderr))
    print(json.dumps(res))
