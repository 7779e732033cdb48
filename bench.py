"""Benchmark: scheduling throughput (pods/sec) on the real TPU chip.

Runs ALL FIVE BASELINE.json configs through the scheduler_perf harness
(benchmarks/scheduler_perf.py — the reference's
test/integration/scheduler_perf YAML workloads), then the CONNECTED path
(benchmarks/connected.py — informers + queue + incremental cache + gang step
+ async binding against the in-process apiserver).

Headline metric mirrors scheduler_perf's SchedulingThroughput on the
MixedHeterogeneous 10k pods x 5k nodes workload: scheduling *decisions* per
second through the filter/score/select cycle. p99 per-pod schedule latency
is reported per workload (north-star target: p99 < 1s).

vs_baseline: ratio against 300 pods/s — the mid-range of upstream
scheduler_perf thresholds for comparable workloads (BASELINE.md; the
reference publishes no in-repo numbers, "published": {}).

Env knobs: BENCH_CASE (only this case), BENCH_SCALE (default 1.0),
BENCH_BATCH (default 1024), BENCH_CONNECTED=0 to skip the connected run,
BENCH_CONNECTED_PODS/NODES (default 2000/1000), BENCH_CONNECTED_PIPELINE
(dispatch-pipeline depth for the connected run — sweep it to find the
knee; unset = SchedulerConfiguration.pipeline_depth default),
BENCH_CHAOS=0 to skip the ChaosChurn case (BENCH_CHAOS_PODS/NODES size
it; KTPU_CHAOS_SEED replays a failing fault schedule — the case exits
the bench non-zero if any pod is lost under faults),
BENCH_SCALEFLEET=0 to skip the ScaleFleet sweep (BENCH_SCALE_NODES
sizes the two-point fleet sweep, default "256 2048"; the 100k campaign
tier is "1250 10000"; BENCH_SCALE_MAX_GROWTH tunes the sublinear
control-plane gate), BENCH_FLEET=0 to skip the FleetChurn case (K
tenant apiservers through one FleetRunner + one warm resident program;
BENCH_FLEET_TENANTS default 4, campaign tier 16; BENCH_FLEET_NOISY
sets the noisy-neighbor churn multiple, BENCH_FLEET_P99 the per-tenant
bind-p99 ceiling — gates: 100% binds/tenant, 0 violations, 0 XLA
compiles in the steady window), BENCH_SLICECARVE=0 to skip the
SliceCarve case (contiguous ICI sub-slice churn over a labeled torus;
BENCH_SLICE_GRID/SHAPE/WINDOW_S/FRAG size it — gates: every gang lands
one contiguous box, 0 violations, 0 XLA compiles in the steady window,
0 carve-parity divergences at every=1),
BENCH_DISASTER=0 to skip the DisasterChurn case
(apiserver SIGKILL + WAL-replay restart mid-churn; BENCH_DISASTER_NODES/
PODS/OUTAGE_S size it, BENCH_DISASTER_BIND_SLO bounds time-to-first-
bind-after-restart), BENCH_WATCHSTORM=0 to skip the WatchStorm case
(>=10k watchers vs 1 leader + 2 read replicas;
BENCH_WATCHSTORM_WATCHERS/PODS size it, BENCH_WATCHSTORM_SPAN_GROWTH
gates leader fan-out span growth, BENCH_WATCHSTORM_HEAL_SLO bounds a
SIGKILLed replica's rebirth — every gate treats a missing number as
failure), BENCH_SCENARIO=0 to skip the ScenarioReplay case (cluster
time machine: BENCH_SCENARIO=builtin:<name> or a .trace.jsonl path
picks the trace, default builtin:smoke; BENCH_SCENARIO_SPEED warps
replay time, BENCH_SCENARIO_SEED seeds the generator — gates: 100% of
trace-resident pods bound, per-phase p99 attempt latency present,
deterministic dispatch order, the manifest's own sloGates),
BENCH_PLANNER=0 to skip the PlannerLoop case (three planners, one
cluster image: autoscaler + descheduler + gang defrag riding the
scheduler's device-resident encoding; BENCH_PLANNER_NODES/CYCLES size
it — gates: 0 XLA compiles and 0 cold full encodes in the steady
window, overlay hits advance for every planner, resident-vs-cold plan
parity bit-equal).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_PODS_PER_SEC = 300.0
HEADLINE = ("MixedHeterogeneous", "10000Pods5000Nodes")


def main():
    # informer bursts compete for the GIL with the resolver's device_get; a
    # finer switch interval shortens the stalls it suffers mid-burst. Set
    # ONCE for the whole bench process so every case runs under the same
    # scheduling regime. Bench-only: the product sets it under
    # KTPU_SWITCH_INTERVAL alone (ROADMAP D9), and chip_smoke.py does not.
    sys.setswitchinterval(0.0005)
    from benchmarks.connected import run_connected
    from benchmarks.scheduler_perf import load_config, run_workload

    only_case = os.environ.get("BENCH_CASE")
    scale = float(os.environ.get("BENCH_SCALE", "1.0"))
    batch = int(os.environ.get("BENCH_BATCH", "1024"))

    log = lambda *a: print(*a, file=sys.stderr)  # noqa: E731
    results = []
    for case in load_config():
        if only_case and case["name"] != only_case:
            continue
        for wl in case["workloads"]:
            if "performance" not in (wl.get("labels") or []):
                continue
            t0 = time.time()
            log(f"[bench] {case['name']}/{wl['name']} ...")
            res = run_workload(case, wl, scale=scale, batch=batch, log=log)
            res["total_s"] = round(time.time() - t0, 1)
            results.append(res)
            log("[bench] " + json.dumps(res))

    # Perfetto traces of each connected-path case's measured window land
    # next to the result JSON, case-suffixed (BENCH_TRACE.<Case>.json;
    # BENCH_TRACE_PATH="" disables; load at ui.perfetto.dev). Set before
    # ALL cases — ChaosChurn/ExplainAB dump traces too.
    os.environ.setdefault(
        "BENCH_TRACE_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_TRACE.json"))

    connected = None
    if os.environ.get("BENCH_CONNECTED", "1") != "0" and not only_case:
        log("[bench] connected-path run ...")
        _pipe = os.environ.get("BENCH_CONNECTED_PIPELINE")
        connected = run_connected(
            n_pods=int(os.environ.get("BENCH_CONNECTED_PODS", "10000")),
            n_nodes=int(os.environ.get("BENCH_CONNECTED_NODES", "5000")),
            pipeline_depth=int(_pipe) if _pipe else None,
            log=log)
        log("[bench] " + json.dumps(connected))

    connected_mesh = None
    shapes = []
    if os.environ.get("BENCH_MESH", "1") != "0" and not only_case:
        from kubernetes_tpu.parallel.mesh import parse_mesh_shape
        # BENCH_MESH_SHAPES: ";"/space-separated width list ("1x2;1x4");
        # falls back to the single-shape BENCH_MESH_SHAPE. "off"/"none"
        # (parse -> None) or an unparseable value disables the case —
        # never silently substitutes a default shape
        shape_s = os.environ.get(
            "BENCH_MESH_SHAPES", os.environ.get("BENCH_MESH_SHAPE", "1x2"))
        try:
            shapes = [s for s in
                      (parse_mesh_shape(tok)
                       for tok in shape_s.replace(";", " ").split())
                      if s is not None]
        except ValueError as e:
            log(f"[bench] bad BENCH_MESH_SHAPES={shape_s!r} ({e}); "
                "skipping mesh case")
            shapes = []
    if shapes:
        # IN this process, on the chips it already owns: per mesh width the
        # deterministic sharded-vs-unsharded drain parity gate and a live
        # hollow-kubelet leg against one shared unsharded baseline. One
        # chip (or a CPU) cannot shard: the case then says so instead of
        # timing virtual CPU devices in a child — the sharded-vs-unsharded
        # PARITY on virtual devices lives in tests/test_mesh*.py.
        from benchmarks.connected import device_block
        dev = device_block()
        if dev["platform"] != "cpu" and dev["count"] >= 2:
            from benchmarks.connected import run_connected_mesh
            log(f"[bench] connected mesh run ({shape_s}) ...")
            # a leaked KTPU_MESH would override BOTH legs' mesh_shape
            # config (including the unsharded leg's explicit None),
            # silently turning the A/B into sharded-vs-sharded
            os.environ.pop("KTPU_MESH", None)
            connected_mesh = run_connected_mesh(
                mesh_shapes=shapes,
                n_pods=int(os.environ.get("BENCH_MESH_PODS", "1024")),
                n_nodes=int(os.environ.get("BENCH_MESH_NODES", "96")),
                batch_size=int(os.environ.get("BENCH_MESH_BATCH", "128")),
                slo_gates={
                    "SchedulingThroughput":
                        float(os.environ.get("BENCH_MESH_SLO_TPUT", "60")),
                    "p99AttemptLatencySeconds":
                        float(os.environ.get("BENCH_MESH_SLO_P99", "10")),
                },
                # the same floor the connected.py mesh main keeps: no
                # sharded-vs-unsharded ratio has been measured on real
                # chips at any size, so the 1.0 goal waits for ROADMAP S7
                min_ratio=float(os.environ.get("BENCH_MESH_MIN_RATIO",
                                               "0.7")),
                log=log)
        else:
            connected_mesh = {
                "case": "ConnectedMesh", "measured": "not measured",
                "reason": (f"needs >= 2 accelerator devices; this process "
                           f"has {dev['count']} x {dev['platform']}"),
                "device": dev}
        log("[bench] " + json.dumps(connected_mesh))

    chaos_churn = None
    if os.environ.get("BENCH_CHAOS", "1") != "0" and not only_case:
        # churn workload under the default fault schedule: API storms,
        # watch gaps, a breaker-tripping device burst, thread stalls. The
        # seed is logged and env-overridable (KTPU_CHAOS_SEED) so any
        # failure replays deterministically; the gate below exits non-zero
        # if a single pod was lost.
        from benchmarks.connected import run_chaos_churn
        log("[bench] chaos churn run ...")
        chaos_churn = run_chaos_churn(
            n_pods=int(os.environ.get("BENCH_CHAOS_PODS", "2000")),
            n_nodes=int(os.environ.get("BENCH_CHAOS_NODES", "1000")),
            log=log)
        log("[bench] " + json.dumps(chaos_churn))

    explain_ab = None
    if os.environ.get("BENCH_EXPLAIN_AB", "1") != "0" and not only_case:
        # explainer + flight recorder on/off A/B on the churn workload:
        # the observability layer must cost <= 5% throughput (hard gate,
        # like the other sloGates — a missing ratio fails too)
        from benchmarks.connected import run_explain_ab
        log("[bench] explain A/B run ...")
        explain_ab = run_explain_ab(
            n_pods=int(os.environ.get("BENCH_EXPLAIN_PODS", "2000")),
            n_nodes=int(os.environ.get("BENCH_EXPLAIN_NODES", "1000")),
            min_ratio=float(os.environ.get("BENCH_EXPLAIN_MIN_RATIO",
                                           "0.95")),
            log=log)
        log("[bench] " + json.dumps(explain_ab))

    preemption = None
    if os.environ.get("BENCH_PREEMPTION", "1") != "0" and not only_case:
        from benchmarks.preemption_bench import run_preemption
        log("[bench] preemption run ...")
        preemption = run_preemption(
            n_nodes=int(os.environ.get("BENCH_PREEMPT_NODES", "5000")),
            n_preemptors=int(os.environ.get("BENCH_PREEMPT_PODS", "128")),
            log=log)
        log("[bench] " + json.dumps(preemption))

    pallas = None
    if os.environ.get("BENCH_PALLAS", "1") != "0" and not only_case:
        # the domain-count hot op: live XLA number + the recorded round-4
        # measurement that retired the Pallas kernel (prove-or-delete);
        # in-process — the bench already owns the single TPU client
        from benchmarks.pallas_bench import run_domain_count
        log("[bench] domain-count hot-op run ...")
        pallas = run_domain_count()
        log("[bench] " + json.dumps(pallas))

    connected_preemption = None
    if os.environ.get("BENCH_CPREEMPT", "1") != "0" and not only_case:
        from benchmarks.connected import run_connected_preemption
        log("[bench] connected preemption run ...")
        connected_preemption = run_connected_preemption(
            n_nodes=int(os.environ.get("BENCH_CPREEMPT_NODES", "5000")),
            n_high=int(os.environ.get("BENCH_CPREEMPT_PODS", "128")),
            log=log)
        log("[bench] " + json.dumps(connected_preemption))

    scale_fleet = None
    if os.environ.get("BENCH_SCALEFLEET", "1") != "0" and not only_case:
        # two-point hollow-fleet sweep with the sublinear control-plane
        # gate (heartbeat + lease + status span growth <= 2x across an 8x
        # fleet; missing number = failure). BENCH_SCALE_NODES sizes the
        # sweep — default fits the box, the 100k campaign runs
        # "1250 10000". Runs before kubemark for the same
        # leftover-daemon-thread reason.
        from benchmarks.scalefleet import run_scale_fleet
        log("[bench] scale-fleet sweep ...")
        sizes = [int(t) for t in os.environ.get(
            "BENCH_SCALE_NODES", "256 2048").replace(",", " ").split()]
        scale_fleet = run_scale_fleet(
            fleet_sizes=sizes,
            n_pods=int(os.environ.get("BENCH_SCALE_PODS", "256")),
            window_s=float(os.environ.get("BENCH_SCALE_WINDOW_S", "12")),
            heartbeat_period=float(os.environ.get("BENCH_SCALE_HB_PERIOD",
                                                  "5.0")),
            max_growth=float(os.environ.get("BENCH_SCALE_MAX_GROWTH",
                                            "2.0")),
            log=log)
        log("[bench] " + json.dumps(scale_fleet))

    fleet_churn = None
    if os.environ.get("BENCH_FLEET", "1") != "0" and not only_case:
        # K tenant apiservers + hollow fleets through ONE FleetRunner and
        # one warm resident program: 100% binds per tenant, per-tenant SLO
        # gates with tenant 0 churning 4x (noisy neighbor), steady-state
        # resident-ctx rebuilds == 0, fail-fast auditor (cross_tenant
        # invariant live) — missing number = failure. Default K=4 fast;
        # campaign tier BENCH_FLEET_TENANTS=16.
        from benchmarks.fleetchurn import run_fleet_churn
        log("[bench] fleet churn run ...")
        fleet_churn = run_fleet_churn(
            n_tenants=int(os.environ.get("BENCH_FLEET_TENANTS", "4")),
            nodes_per_tenant=int(os.environ.get("BENCH_FLEET_NODES", "8")),
            upfront_pods=int(os.environ.get("BENCH_FLEET_PODS", "24")),
            window_s=float(os.environ.get("BENCH_FLEET_WINDOW_S", "12")),
            noisy_factor=int(os.environ.get("BENCH_FLEET_NOISY", "4")),
            p99_slo_s=float(os.environ.get("BENCH_FLEET_P99", "10")),
            log=log)
        log("[bench] " + json.dumps(fleet_churn))

    slice_carve = None
    if os.environ.get("BENCH_SLICECARVE", "1") != "0" and not only_case:
        # contiguous-slice churn over a labeled torus: every gang must
        # land one contiguous box, with 0 violations (slice_contiguity
        # armed), 0 XLA compiles in the steady window, and every device
        # carve parity-confirmed against the numpy oracle carver
        from benchmarks.slicecarve import run_slice_carve
        log("[bench] slice carve run ...")
        slice_carve = run_slice_carve(
            grid=os.environ.get("BENCH_SLICE_GRID", "4x4x2"),
            shape=os.environ.get("BENCH_SLICE_SHAPE", "2x2x2"),
            window_s=float(os.environ.get("BENCH_SLICE_WINDOW_S", "10")),
            n_fragment=int(os.environ.get("BENCH_SLICE_FRAG", "4")),
            log=log)
        log("[bench] " + json.dumps(slice_carve))

    disaster = None
    if os.environ.get("BENCH_DISASTER", "1") != "0" and not only_case:
        # apiserver SIGKILL + WAL-replay restart mid-churn: every pod
        # bound, 0 invariant violations, 0 outage-caused evictions/taints
        # (disruption mode engaged AND released), first-bind-after-restart
        # <= BENCH_DISASTER_BIND_SLO (10s) — missing number = failure
        from benchmarks.disaster import run_disaster_churn
        log("[bench] disaster churn run ...")
        disaster = run_disaster_churn(
            n_hollow=int(os.environ.get("BENCH_DISASTER_NODES", "48")),
            n_pods=int(os.environ.get("BENCH_DISASTER_PODS", "96")),
            outage_s=float(os.environ.get("BENCH_DISASTER_OUTAGE_S",
                                          "16")),
            bind_slo_s=float(os.environ.get("BENCH_DISASTER_BIND_SLO",
                                            "10")),
            log=log)
        log("[bench] " + json.dumps(disaster))

    watch_storm = None
    if os.environ.get("BENCH_WATCHSTORM", "1") != "0" and not only_case:
        # read-replica serving plane under a watch storm: >=10k watchers
        # against 1 leader + 2 replicas — leader fan-out span growth
        # <= 1.2x with >= 2/3 replica-served share, gap-free streams
        # (signature-identical per cohort), 0 drops, staleness bound
        # honored, replica SIGKILL mid-churn heals with zero loss.
        # BENCH_WATCHSTORM_WATCHERS/PODS size it; before kubemark for the
        # same daemon-thread-pollution reason as the others
        from benchmarks.watchstorm import run_watch_storm
        log("[bench] watch storm run ...")
        watch_storm = run_watch_storm(
            n_watchers=int(os.environ.get("BENCH_WATCHSTORM_WATCHERS",
                                          "10500")),
            churn_pods=int(os.environ.get("BENCH_WATCHSTORM_PODS", "600")),
            span_growth_max=float(os.environ.get(
                "BENCH_WATCHSTORM_SPAN_GROWTH", "1.2")),
            heal_slo_s=float(os.environ.get("BENCH_WATCHSTORM_HEAL_SLO",
                                            "90")),
            log=log)
        log("[bench] " + json.dumps(watch_storm))

    planner_loop = None
    if os.environ.get("BENCH_PLANNER", "1") != "0" and not only_case:
        # three planners, one cluster image: the BackgroundPlanner cadence
        # drives autoscaler + descheduler + gang defrag against the
        # scheduler's device-resident encoding — gates: 0 XLA compiles and
        # 0 cold full encodes across the measured window, every planner's
        # overlay hits advance, resident-vs-cold plans bit-equal, 0
        # invariant violations — missing number = failure
        from benchmarks.plannerloop import run_planner_loop
        log("[bench] planner loop run ...")
        planner_loop = run_planner_loop(
            n_nodes=int(os.environ.get("BENCH_PLANNER_NODES", "8")),
            window_cycles=int(os.environ.get("BENCH_PLANNER_CYCLES", "6")),
            log=log)
        log("[bench] " + json.dumps(planner_loop))

    scenario = None
    _scen = os.environ.get("BENCH_SCENARIO", "1")
    if _scen != "0" and not only_case:
        # cluster time machine: replay a production-shaped trace
        # (builtin:<name> or a .trace.jsonl path — committed fixture, WAL
        # capture, or audit-bundle conversion) through the connected
        # stack under the fail-fast auditor. Gates: 100% of trace-
        # resident pods bound, per-phase p99 attempt latency present,
        # deterministic dispatch order, the manifest's own sloGates —
        # missing numbers fail. BENCH_SCENARIO=1 runs builtin:smoke;
        # BENCH_SCENARIO_SPEED warps replay time (default 4x compressed).
        from benchmarks.scenario import run_scenario_replay
        log("[bench] scenario replay run ...")
        scenario = run_scenario_replay(
            spec="builtin:smoke" if _scen == "1" else _scen,
            speed=float(os.environ.get("BENCH_SCENARIO_SPEED", "4")),
            seed=int(os.environ.get("BENCH_SCENARIO_SEED", "0")),
            log=log)
        log("[bench] " + json.dumps(scenario))

    kubemark = None
    if os.environ.get("BENCH_KUBEMARK", "1") != "0" and not only_case:
        # LAST on purpose: the hollow fleet leaves hundreds of daemon
        # threads behind in this process, which measurably degrades any
        # device-path phase that runs after it on the single-core box
        from benchmarks.kubemark import run_kubemark
        log("[bench] kubemark run ...")
        kubemark = run_kubemark(
            n_hollow=int(os.environ.get("BENCH_KUBEMARK_NODES", "500")),
            n_pods=int(os.environ.get("BENCH_KUBEMARK_PODS", "1000")),
            log=log)
        log("[bench] " + json.dumps(kubemark))

    head = next((r for r in results
                 if (r["case"], r["workload"]) == HEADLINE), None)
    is_headline = head is not None
    if head is None:
        head = results[-1] if results else {"SchedulingThroughput": 0.0,
                                            "pods": 0, "nodes": 0,
                                            "case": "none", "workload": ""}
    throughput = head.get("SchedulingThroughput") or 0.0
    out = {
        "metric": (f"scheduling throughput ({head['case']} "
                   f"{head.get('pods', 0)}x{head.get('nodes', 0)})"),
        "value": round(throughput, 1),
        "unit": "pods/sec",
        # the 300 pods/s baseline is calibrated for the headline workload;
        # a filtered run (BENCH_CASE) has no comparable baseline
        "vs_baseline": (round(throughput / BASELINE_PODS_PER_SEC, 2)
                        if is_headline else None),
        "p99_schedule_latency_s": head.get("p99_schedule_latency_s"),
        "all_passed": all(r["passed"] for r in results) if results else False,
        "workloads": [
            # decision-latency cases (ClusterAutoscalerScaleUp,
            # DeschedulerDefrag) carry no SchedulingThroughput — a KeyError
            # here used to abort the whole summary (and the divergence gate
            # below) after every case had already passed
            {"case": r["case"], "workload": r["workload"],
             "pods_per_sec": r.get("SchedulingThroughput"),
             "p99_s": r.get("p99_schedule_latency_s"),
             "passed": r["passed"],
             **({"slo_failures": r["slo_failures"]}
                if r.get("slo_failures") else {}),
             **({"churn_api_ops": r["churn_api_ops"], "connected": True}
                if "churn_api_ops" in r else {})} for r in results],
        "connected": connected,
        "chaos_churn": chaos_churn,
        "connected_mesh": connected_mesh,
        "explain_ab": explain_ab,
        "preemption": preemption,
        "connected_preemption": connected_preemption,
        "scale_fleet": scale_fleet,
        "fleet_churn": fleet_churn,
        "slice_carve": slice_carve,
        "disaster_churn": disaster,
        "watch_storm": watch_storm,
        "planner_loop": planner_loop,
        "scenario_replay": scenario,
        "kubemark": kubemark,
        "pallas": pallas,
        # confirmed correctness-invariant violations across every audited
        # case (connected / chaos / mesh legs). _require_invariant_field
        # refuses to emit a summary without this key: BENCH_r05's
        # parsed-null crash taught that a silently missing figure reads
        # as "fine" for rounds
        "invariant_violations": _sum_violations(connected, chaos_churn,
                                                connected_mesh, explain_ab,
                                                scale_fleet, disaster,
                                                fleet_churn, slice_carve,
                                                watch_storm, planner_loop,
                                                scenario),
        # hard SLO verdicts from case-config gates (SchedulingChurn p99 +
        # throughput, ConnectedMesh legs). Missing numbers are failures —
        # the BENCH_r05 parsed-null lesson: a silently absent figure must
        # never read as a pass.
        "slo_failures": _collect_slo_failures(results, connected_mesh,
                                              explain_ab, scale_fleet,
                                              disaster, fleet_churn,
                                              slice_carve, watch_storm,
                                              scenario,
                                              planner_loop=planner_loop),
    }
    _require_invariant_field(out, "bench summary")
    print(json.dumps(out))
    if connected is not None:
        # hard gate: every pod binds on the host oracle too — a connected
        # figure only counts if the device program produced the answers
        from benchmarks.connected import check_served_on_device
        hidden = check_served_on_device(connected)
        if hidden:
            print(f"[bench] FATAL: the connected run did not stay on the "
                  f"device path: {hidden}", file=sys.stderr)
            sys.exit(1)
    if out["slo_failures"]:
        print(f"[bench] FATAL: {len(out['slo_failures'])} SLO gate "
              f"failure(s): {out['slo_failures']}", file=sys.stderr)
        sys.exit(1)
    if out["invariant_violations"]:
        audited = {name: c.get("invariant_violations") for name, c in
                   (("connected", connected), ("chaos_churn", chaos_churn),
                    ("connected_mesh", connected_mesh),
                    ("scale_fleet", scale_fleet),
                    ("fleet_churn", fleet_churn),
                    ("slice_carve", slice_carve),
                    ("disaster_churn", disaster),
                    ("watch_storm", watch_storm),
                    ("scenario_replay", scenario)) if c}
        print(f"[bench] FATAL: {out['invariant_violations']} correctness-"
              f"invariant violation(s) confirmed by the auditor "
              f"({audited}); repro bundles are on disk — replay with the "
              "logged chaos seed", file=sys.stderr)
        sys.exit(1)
    if chaos_churn is not None and (chaos_churn.get("chaos") or {}) \
            .get("lost"):
        # hard gate: pods lost under the fault schedule means self-healing
        # failed somewhere — replay with the logged seed to localize it
        print(f"[bench] FATAL: ChaosChurn lost "
              f"{chaos_churn['chaos']['lost']} pods "
              f"(seed {chaos_churn['chaos']['seed']})", file=sys.stderr)
        sys.exit(1)
    if (connected_mesh is not None
            and (connected_mesh.get("parity") or {}).get("ok") is False):
        # hard gate: a mesh whose placements diverge from single-device is
        # a miscompile or a sharding bug, never a tolerable perf variance
        print("[bench] FATAL: ConnectedMesh sharded placements diverge "
              "from unsharded", file=sys.stderr)
        sys.exit(1)


def _collect_slo_failures(results, connected_mesh, explain_ab=None,
                          scale_fleet=None, disaster=None,
                          fleet_churn=None, slice_carve=None,
                          watch_storm=None, scenario=None,
                          planner_loop=None) -> list:
    """Flatten every case's hard-SLO failure strings, prefixed by case."""
    out = []
    for r in results or []:
        for msg in r.get("slo_failures") or []:
            out.append(f"{r['case']}/{r['workload']}: {msg}")
    if connected_mesh is not None:
        for msg in connected_mesh.get("slo_failures") or []:
            out.append(f"ConnectedMesh: {msg}")
    if explain_ab is not None:
        for msg in explain_ab.get("slo_failures") or []:
            out.append(f"ExplainAB: {msg}")
    if scale_fleet is not None:
        for msg in scale_fleet.get("slo_failures") or []:
            out.append(f"ScaleFleet: {msg}")
    if disaster is not None:
        for msg in disaster.get("slo_failures") or []:
            out.append(f"DisasterChurn: {msg}")
    if fleet_churn is not None:
        for msg in fleet_churn.get("slo_failures") or []:
            out.append(f"FleetChurn: {msg}")
    if slice_carve is not None:
        for msg in slice_carve.get("slo_failures") or []:
            out.append(f"SliceCarve: {msg}")
    if watch_storm is not None:
        for msg in watch_storm.get("slo_failures") or []:
            out.append(f"WatchStorm: {msg}")
    if scenario is not None:
        for msg in scenario.get("slo_failures") or []:
            out.append(f"ScenarioReplay: {msg}")
    if planner_loop is not None:
        for msg in planner_loop.get("slo_failures") or []:
            out.append(f"PlannerLoop: {msg}")
    return out


def _sum_violations(*cases) -> int:
    """Total invariant violations across audited case results (None cases
    — skipped via env knobs — contribute nothing)."""
    return sum(int(c.get("invariant_violations") or 0)
               for c in cases if c is not None)


def _require_invariant_field(summary: dict, label: str) -> None:
    """Refuse to emit a result JSON whose summary omits
    ``invariant_violations``: a missing correctness figure must fail the
    run loudly, not read as zero (the BENCH_r05 lesson, encoded)."""
    if "invariant_violations" not in summary:
        print(f"[bench] FATAL: {label} omits the invariant_violations "
              "field; refusing to emit it", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    main()
