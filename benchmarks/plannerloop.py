"""PlannerLoop: three planners, one cluster image — the steady-window proof.

One scheduler process holds the device-resident cluster encoding; one
``BackgroundPlanner`` cadence drives the autoscaler's scale-up/scale-down
simulation, the descheduler's eviction planning, and gang defrag against it
every cycle through the shared ``ResidentPlanner`` overlay views.

Hard gates (missing number = failure, PR-8 discipline):
  - ZERO XLA compiles across the measured window (``jax.monitoring``
    backend_compile events, adaptive warmup so lazy variants land before
    the gate arms),
  - zero cold full encodes: the resident decline delta across the window
    is 0 AND the scheduler cache's ``full_encodes`` counter does not move,
  - every planner's overlay hit count ADVANCES in the window (the zero
    above is not vacuous — all three planners really ride the image),
  - resident-vs-cold parity: the same observation planned through the
    overlay view and through today's cold encode path produces bit-equal
    plans (scale-up options, scale-down proof, eviction sets, gang moves),
  - 0 invariant violations under the fail-fast auditor.

Run standalone (``python -m benchmarks.plannerloop``) or via ``bench.py``
with ``BENCH_PLANNER=1``. ``BENCH_PLANNER_DATA_DIR`` runs the apiserver in
durable mode so the run's ``wal.jsonl`` can be converted into a committed
scenario trace (``trace_from_wal``).
"""

from __future__ import annotations

import time


def _norm_scale_up(options) -> list:
    return [(o.group.name, sorted(o.pod_indices), o.nodes_needed,
             round(float(o.waste), 9)) for o in options]


def _norm_scale_down(plan) -> tuple:
    return (sorted(plan.removable),
            {n: sorted(m) for n, m in plan.placements.items()},
            dict(plan.blocked))


def _norm_evictions(plan) -> tuple:
    return ([(s.name, s.strategy, sorted(p.key for p in s.victims),
              sorted(s.moves), s.reason) for s in plan.accepted],
            dict(plan.blocked), plan.batch_victims, plan.batch_sets)


def _norm_gang(plan) -> tuple:
    acc = None
    if plan.accepted is not None:
        acc = (plan.accepted.name, plan.accepted.strategy,
               sorted(p.key for p in plan.accepted.victims),
               sorted(plan.accepted.moves))
    return (plan.gang, acc, sorted(plan.gang_moves),
            plan.fits_without_evictions, dict(plan.blocked))


def run_planner_loop(n_nodes: int = 8, pods_per_node: int = 3,
                     window_cycles: int = 6, max_warmup_cycles: int = 14,
                     quiet_cycles: int = 2, bind_timeout: float = 120.0,
                     data_dir=None, log=lambda *a: None) -> dict:
    from benchmarks.connected import _audit_close, _bench_auditor
    from kubernetes_tpu.autoscaler.autoscaler import ClusterAutoscaler
    from kubernetes_tpu.autoscaler.nodegroup import (
        NODE_GROUP_LABEL, NodeGroup, StaticNodeGroupProvider)
    from kubernetes_tpu.autoscaler.simulator import (
        simulate_scale_down, simulate_scale_up)
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.descheduler.descheduler import (
        Descheduler, DeschedulerConfiguration)
    from kubernetes_tpu.descheduler.strategies import GANG_LABEL
    from kubernetes_tpu.sched.bgplanner import BackgroundPlanner
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.store.apiserver import APIServer
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    server = None
    runner = None
    failures: list[str] = []
    result: dict = {"case": "PlannerLoop",
                    "workload": f"{n_nodes}n_{pods_per_node}ppn_"
                                f"{window_cycles}cyc"}
    try:
        t0 = time.time()
        server = APIServer(data_dir=data_dir).start()
        client = HTTPClient(server.url, timeout=120.0)

        # static fleet, all nodes group-labeled (scale-down candidates via
        # re-adoption); pl-n0 carries ONE small pod so it sits under both
        # the descheduler's HighNodeUtilization threshold (a persistent
        # candidate set every dry-run cycle) and the autoscaler's
        # scale-down threshold (a live removable-node proof every cycle)
        client.nodes().create_many(
            [make_node(f"pl-n{i}")
             .capacity({"cpu": "8", "memory": "32Gi", "pods": "32"})
             .label(NODE_GROUP_LABEL, "pool-a").obj().to_dict()
             for i in range(n_nodes)])
        bound = [make_pod("pl-b0-0", "default")
                 .req({"cpu": "1", "memory": "1Gi"})
                 .node("pl-n0").obj().to_dict()]
        for i in range(1, n_nodes):
            for j in range(pods_per_node):
                bound.append(make_pod(f"pl-b{i}-{j}", "default")
                             .req({"cpu": "2", "memory": "2Gi"})
                             .node(f"pl-n{i}").obj().to_dict())
        client.pods("default").create_many(bound)

        runner = SchedulerRunner(
            HTTPClient(server.url),
            SchedulerConfiguration(batch_size=8, max_drain_batches=1))
        runner.auditor = _bench_auditor(runner, client)
        # no drain loop: the fleet is static, every planner cycle must see
        # a fresh resident image with nothing in flight
        runner.start(wait_sync=60.0, start_loop=False)
        t1 = time.time()
        armed = runner.scheduler.warm_drain(
            [make_pod(f"pl-w{k}", "default").req({"cpu": "2"}).obj()
             for k in range(8)],
            slot_headroom=len(bound) + 64)
        result["seed_s"] = round(t1 - t0, 2)
        log(f"  {n_nodes} nodes + {len(bound)} bound pods in "
            f"{result['seed_s']}s (ctx armed: {armed})")

        # the perpetual planning workload: pods nothing (node or template)
        # can absorb keep the scale-up simulation live every cycle, and a
        # pending gang keeps gang defrag re-planning (descheduler dry-run,
        # so nothing ever executes and the image never churns)
        client.pods("default").create_many(
            [make_pod(f"pl-big{k}", "default")
             .req({"cpu": "64", "memory": "128Gi"}).obj().to_dict()
             for k in range(2)])
        client.pods("default").create_many(
            [make_pod(f"pl-g{k}", "default").req({"cpu": "6"})
             .label(GANG_LABEL, "pl-gang").obj().to_dict()
             for k in range(3)])

        groups = [
            NodeGroup(name="pool-a", min_size=0, max_size=n_nodes + 4,
                      template=make_node("pool-a-template").capacity(
                          {"cpu": "2", "memory": "4Gi", "pods": "16"}).obj()),
            # headroom 0: never provisioned by the loop, but the parity leg
            # hands simulate_scale_up room so a REAL option gets compared
            NodeGroup(name="pool-big", min_size=0, max_size=0,
                      template=make_node("pool-big-template").capacity(
                          {"cpu": "96", "memory": "256Gi",
                           "pods": "32"}).obj()),
        ]
        autoscaler = ClusterAutoscaler(
            HTTPClient(server.url, timeout=60.0),
            StaticNodeGroupProvider(HTTPClient(server.url, timeout=60.0),
                                    groups),
            utilization_threshold=0.5,
            scale_down_unneeded_s=10 ** 9)   # plan every cycle, reclaim never
        descheduler = Descheduler(
            HTTPClient(server.url, timeout=60.0),
            DeschedulerConfiguration())
        planner = BackgroundPlanner(
            client, runner.scheduler, autoscaler=autoscaler,
            descheduler=descheduler, descheduler_dry_run=True,
            warmup_cycles=1)

        # ---- adaptive warmup: cycle until the compile gate stays quiet ----
        t2 = time.time()
        quiet = 0
        warm_used = 0
        while warm_used < max_warmup_cycles and quiet < quiet_cycles:
            s = planner.run_once()
            warm_used += 1
            quiet = quiet + 1 if s.get("steadyCompiles", 1) == 0 else 0
        result["warmup_cycles"] = warm_used
        result["warmup_s"] = round(time.time() - t2, 2)
        log(f"  warmup: {warm_used} cycles in {result['warmup_s']}s "
            f"({quiet} quiet)")
        if quiet < quiet_cycles:
            failures.append(
                f"warmup never went compile-quiet in {warm_used} cycles")

        # ---- measured window ---------------------------------------------
        stats0 = planner.resident.stats()
        enc0 = runner.cache.stats().get("full_encodes", 0)
        compiles = 0
        t3 = time.time()
        for _ in range(window_cycles):
            s = planner.run_once()
            compiles += s.get("steadyCompiles", 0)
        result["window_s"] = round(time.time() - t3, 2)
        result["cycle_ms"] = round(1000 * (time.time() - t3)
                                   / window_cycles, 1)
        stats1 = planner.resident.stats()
        result["window_compiles"] = compiles
        if compiles:
            failures.append(f"{compiles} XLA compiles in the steady window")
        declines = (sum(sum(v.values())
                        for v in stats1["declines"].values())
                    - sum(sum(v.values())
                          for v in stats0["declines"].values()))
        result["window_declines"] = declines
        if declines:
            result["decline_reasons"] = stats1["declines"]
            failures.append(f"{declines} resident declines (cold encodes) "
                            "in the steady window")
        enc_delta = runner.cache.stats().get("full_encodes", 0) - enc0
        result["window_full_encodes"] = enc_delta
        if enc_delta:
            failures.append(f"{enc_delta} scheduler cold full encodes "
                            "in the steady window")
        hits = {}
        for name in ("autoscaler", "descheduler", "gangDefrag"):
            d = (stats1["hits"].get(name, 0) - stats0["hits"].get(name, 0))
            hits[name] = d
            if d <= 0:
                failures.append(f"planner {name}: overlay hits did not "
                                f"advance in the window ({d})")
        result["window_hits"] = hits
        result["spans_s"] = {k: round(v, 4)
                             for k, v in planner._spans.items()}
        log(f"  window: {window_cycles} cycles, {compiles} compiles, "
            f"{declines} declines, hits {hits}")

        # ---- resident-vs-cold parity (same observation, both paths) ------
        nodes_o, pods_o, pod_dicts_o = autoscaler._observe()
        bound_o = [p for p in pods_o if p.spec.node_name]
        pending_o = autoscaler._pending(pods_o)
        headroom = {"pool-a": 4, "pool-big": 2}  # force a real option
        up = [_norm_scale_up(simulate_scale_up(
            nodes_o, bound_o, pending_o, groups, headroom=headroom,
            encoder=autoscaler.encoder, resident=r))
            for r in (planner.resident, None)]
        candidates = [n.metadata.name for n in nodes_o]
        down = [_norm_scale_down(simulate_scale_down(
            nodes_o, bound_o, candidates, utilization_threshold=0.5,
            all_pod_dicts=pod_dicts_o, encoder=autoscaler.encoder,
            resident=r)) for r in (planner.resident, None)]
        obs = descheduler._observe()
        dplans = []
        for r in (planner.resident, None):
            descheduler.resident = r
            ep, gps = descheduler.plan(*obs)
            dplans.append((_norm_evictions(ep),
                           [_norm_gang(g) for g in gps]))
        descheduler.resident = planner.resident
        parity = {"scale_up": up[0] == up[1], "scale_down": down[0] == down[1],
                  "evictions": dplans[0][0] == dplans[1][0],
                  "gang_defrag": dplans[0][1] == dplans[1][1]}
        result["plan_parity"] = parity
        result["parity_scale_up_options"] = len(up[1])
        result["parity_gang_plans"] = len(dplans[1][1])
        for leg, ok in parity.items():
            if not ok:
                failures.append(f"resident/cold plan divergence: {leg}")
        if not up[1]:
            failures.append("parity scale-up produced no options "
                            "(vacuous comparison)")
        log(f"  parity: {parity} ({len(up[1])} scale-up options, "
            f"{len(dplans[1][1])} gang plans)")

        result["planner_status"] = planner.status()
        result["overlay"] = stats1
        if data_dir:
            import os
            # retire the perpetually-pending planning workload so the
            # captured WAL converts to a replayable trace: a scenario
            # replay gates 100% binding on pods the trace leaves resident
            for k in range(2):
                client.pods("default").delete(f"pl-big{k}")
            for k in range(3):
                client.pods("default").delete(f"pl-g{k}")
            result["wal_path"] = os.path.join(data_dir, "wal.jsonl")
    finally:
        try:
            if runner is not None:
                result.update(_audit_close(runner))
        finally:
            if server is not None:
                server.stop()
    if "invariant_violations" not in result:
        result["invariant_violations"] = None
        failures.append("no invariant audit ran")
    result["slo_failures"] = failures
    return result


if __name__ == "__main__":
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    res = run_planner_loop(
        n_nodes=int(os.environ.get("BENCH_PLANNER_NODES", "8")),
        window_cycles=int(os.environ.get("BENCH_PLANNER_CYCLES", "6")),
        data_dir=os.environ.get("BENCH_PLANNER_DATA_DIR") or None,
        log=lambda *a: print(*a, file=sys.stderr, flush=True))
    print(json.dumps(res, indent=2, default=str))
    if res.get("slo_failures") or res.get("invariant_violations"):
        sys.exit(1)
