"""scheduler_perf — YAML-driven scheduling benchmark harness.

Reference: ``test/integration/scheduler_perf/scheduler_perf.go``
(``BenchmarkPerfScheduling``: each test case is an op list — createNodes,
createPods[, churn] — bound to named workloads via ``$param`` substitution;
the SchedulingThroughput collector measures pods/s over the
``collectMetrics: true`` pods; per-workload thresholds gate pass/fail;
``labels`` select subsets like the upstream ``performance``/``short`` tags).

The execution engine here is the TPU gang scheduler driven in-process (the
measured cycle is filter->score->select, exactly what the reference's
collector measures — binding is async in both).

Usage:
  python benchmarks/scheduler_perf.py [--labels short] [--case SchedulingBasic]
                                      [--scale 0.1] [--serial-oracle]
Emits one JSON line per workload:
  {"case": ..., "workload": ..., "SchedulingThroughput": ..., "passed": ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

CONFIG_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "config")


def _sub(value, params):
    """$param substitution (scheduler_perf's countParam convention)."""
    if isinstance(value, str) and value.startswith("$"):
        return params[value[1:]]
    return value


def load_config(path=None):
    import yaml
    path = path or os.path.join(CONFIG_DIR, "performance-config.yaml")
    with open(path) as f:
        return yaml.safe_load(f)


def _load_template(rel_path):
    import yaml
    with open(os.path.join(CONFIG_DIR, rel_path)) as f:
        return yaml.safe_load(f)


def materialize(case: dict, params: dict):
    """Run the op list host-side -> (nodes, measured_pods, warm_pods)."""
    from kubernetes_tpu.api.types import Node, Pod

    nodes: list = []
    measured: list = []
    warm: list = []
    for op in case["workloadTemplate"]:
        code = op["opcode"]
        if code == "createNodes":
            count = int(_sub(op.get("countParam", op.get("count", 0)), params))
            tpl = _load_template(op["nodeTemplatePath"])
            strat = op.get("labelStrategy")
            for i in range(count):
                d = json.loads(json.dumps(tpl))
                md = d.setdefault("metadata", {})
                md["name"] = f"{md.pop('generateName', 'node-')}{i}"
                if strat:
                    md.setdefault("labels", {})[strat["key"]] = \
                        strat["values"][i % len(strat["values"])]
                md.setdefault("labels", {})["kubernetes.io/hostname"] = md["name"]
                nodes.append(Node.from_dict(d))
        elif code == "createPods":
            count = int(_sub(op.get("countParam", op.get("count", 0)), params))
            tpl = _load_template(op["podTemplatePath"])
            out = measured if op.get("collectMetrics") else warm
            for i in range(count):
                d = json.loads(json.dumps(tpl))
                md = d.setdefault("metadata", {})
                md["name"] = f"{md.pop('generateName', 'pod-')}{len(out)}-{i}"
                out.append(Pod.from_dict(d))
        elif code in ("simulateAutoscale", "simulateDefrag"):
            pass  # handled by the dedicated workload runner after materialize
        elif code == "generateWorkload":
            from benchmarks.workloads import WORKLOADS
            gen = WORKLOADS[op["generator"]]
            n_nodes = int(_sub(op["nodesParam"], params))
            n_pods = int(_sub(op["podsParam"], params))
            g_nodes, g_pods = gen(pods=n_pods, nodes=n_nodes)
            nodes.extend(g_nodes)
            (measured if op.get("collectMetrics") else warm).extend(g_pods)
        else:
            raise ValueError(f"unknown opcode {code!r}")
    return nodes, measured, warm


def run_workload(case: dict, workload: dict, scale: float = 1.0,
                 batch: int = 1024, log=lambda *a: None):
    """-> result dict with SchedulingThroughput + threshold verdicts."""
    from kubernetes_tpu.encode.snapshot import SnapshotEncoder
    from kubernetes_tpu.models.gang import gang_drain, prepare_drain

    params = {k: max(1, int(v * scale)) for k, v in workload["params"].items()}
    churn_op = next((op for op in case["workloadTemplate"]
                     if op["opcode"] == "churn"), None)
    if churn_op is not None:
        return _run_churn_workload(case, workload, params, churn_op, log,
                                   scale=scale, batch=batch)
    autoscale_op = next((op for op in case["workloadTemplate"]
                         if op["opcode"] == "simulateAutoscale"), None)
    if autoscale_op is not None:
        return _run_autoscaler_workload(case, workload, params,
                                        autoscale_op, log, scale=scale)
    defrag_op = next((op for op in case["workloadTemplate"]
                      if op["opcode"] == "simulateDefrag"), None)
    if defrag_op is not None:
        return _run_descheduler_workload(case, workload, params,
                                         defrag_op, log, scale=scale)
    nodes, measured, warm = materialize(case, params)
    log(f"  materialized {len(nodes)} nodes, {len(measured)} measured pods")

    enc = SnapshotEncoder()
    t0 = time.time()
    ct, meta = enc.encode_cluster(nodes, warm, pending_pods=measured,
                                  pending_slots=False)
    batches = [measured[i:i + batch] for i in range(0, len(measured), batch)]
    pbs = [enc.encode_pods(b, meta) for b in batches]
    topo_keys = meta.topo_keys
    # prepare_drain stages the cluster + queue tensors into HBM (a live
    # scheduler keeps them resident and patches deltas — sched/cache.py);
    # staging counts as encode time, not scheduling time.
    plan = prepare_drain(ct, pbs)
    encode_s = time.time() - t0

    # warmup compile (excluded, as upstream excludes informer warmup):
    # the drain is one program, so warmup = one full run on the same shapes
    t0 = time.time()
    gang_drain(topo_keys=topo_keys, prepared=plan)
    compile_s = time.time() - t0

    # The measured run drains the WHOLE queue as one device program
    # (lax.scan over batches — see models/gang.py gang_drain): one dispatch,
    # one readback; capacity and relational state carry batch to batch
    # exactly like the reference's sequential loop.
    t0 = time.time()
    assignments, rounds, _ = gang_drain(topo_keys=topo_keys, prepared=plan)
    dt = time.time() - t0
    scheduled = 0
    for b, chunk in enumerate(batches):
        scheduled += int((assignments[b][:len(chunk)] >= 0).sum())
    throughput = scheduled / dt if dt > 0 else 0.0
    # p99 per-pod schedule latency: every pod in a batch experiences its
    # batch's filter->score->select window (the decision is batch-atomic,
    # matching what scheduler_perf's attempt-duration metric measures). The
    # drain is one fused program, so batch windows are attributed from the
    # per-batch convergence round counts the device reports.
    total_rounds = max(int(rounds.sum()), 1)
    batch_s = [dt * int(r) / total_rounds for r in rounds]
    per_pod = np.repeat(batch_s[:len(batches)],
                        [len(c) for c in batches])
    p99 = float(np.percentile(per_pod, 99)) if per_pod.size else 0.0

    thresholds = workload.get("thresholds") or {}
    passed = all(throughput >= t * scale if k == "SchedulingThroughput" else True
                 for k, t in thresholds.items())
    if "p99ScheduleLatencySeconds" in thresholds:
        passed = passed and p99 <= thresholds["p99ScheduleLatencySeconds"]
    return {
        "case": case["name"], "workload": workload["name"],
        "SchedulingThroughput": round(throughput, 1),
        "p99_schedule_latency_s": round(p99, 4),
        "scheduled": scheduled, "pods": len(measured), "nodes": len(nodes),
        "encode_s": round(encode_s, 2), "compile_s": round(compile_s, 2),
        "measure_s": round(dt, 2),
        "thresholds": thresholds, "passed": passed,
    }


def _run_autoscaler_workload(case: dict, workload: dict, params: dict,
                             op: dict, log, scale: float = 1.0) -> dict:
    """The ``simulateAutoscale`` opcode: a full cluster (warm pods bound
    round-robin), the measured pods pending, and K candidate node groups
    evaluated by the batched tensor scale-up simulation — the measured
    quantity is the autoscaler DECISION latency (one ``run_filters`` over
    all K expansion hypotheses + the per-group binpack + the expander).
    Reference workload shape: the reference autoscaler's scalability tests
    measure the same RunOnce simulate phase."""
    from kubernetes_tpu.autoscaler.expander import EXPANDERS
    from kubernetes_tpu.autoscaler.nodegroup import load_node_group
    from kubernetes_tpu.autoscaler.simulator import simulate_scale_up

    nodes, measured, warm = materialize(case, params)
    # warm pods model the existing load: bind them round-robin so the
    # initial cluster is genuinely full for the pending set
    for i, p in enumerate(warm):
        p.spec.node_name = nodes[i % len(nodes)].metadata.name
    groups = [load_node_group(_load_template(path))
              for path in op["nodeGroupTemplatePaths"]]
    expander = EXPANDERS[op.get("expander", "least-waste")]
    log(f"  {len(nodes)} full nodes, {len(measured)} pending pods, "
        f"{len(groups)} candidate groups")

    # warmup excluded (JIT compile of the filter program), as everywhere
    t0 = time.time()
    simulate_scale_up(nodes, warm, measured, groups)
    compile_s = time.time() - t0
    t0 = time.time()
    options = simulate_scale_up(nodes, warm, measured, groups)
    decision_s = time.time() - t0
    choice = expander(options, seed=0)

    placed = choice.pods_placed if choice else 0
    thresholds = workload.get("thresholds") or {}
    passed = placed >= len(measured)
    if "ScaleUpDecisionSeconds" in thresholds:
        passed = passed and decision_s <= thresholds["ScaleUpDecisionSeconds"]
    return {
        "case": case["name"], "workload": workload["name"],
        "ScaleUpDecisionSeconds": round(decision_s, 4),
        "compile_s": round(compile_s, 2),
        "candidate_groups": len(groups),
        "pods_placed": placed, "pods": len(measured), "nodes": len(nodes),
        "chosen_group": choice.group.name if choice else None,
        "nodes_needed": choice.nodes_needed if choice else 0,
        "thresholds": thresholds, "passed": passed,
    }


def _run_descheduler_workload(case: dict, workload: dict, params: dict,
                              op: dict, log, scale: float = 1.0) -> dict:
    """The ``simulateDefrag`` opcode: a deliberately fragmented cluster
    (warm pods scattered one per node so no node can host a gang member)
    plus a pending gang — the measured quantity is the gang-defrag PLAN
    latency: one batched ``run_filters`` over every candidate drain prefix
    AND the gang, then the host-side fewest-evictions ledger scan
    (kubernetes_tpu/descheduler/planner.py plan_gang_defrag)."""
    from kubernetes_tpu.descheduler import (
        gang_consolidation_candidates,
        plan_gang_defrag,
    )

    nodes, measured, warm = materialize(case, params)
    for i, p in enumerate(warm):
        p.spec.node_name = nodes[i % len(nodes)].metadata.name
    max_nodes = int(_sub(op.get("maxDrainNodesParam",
                                op.get("maxDrainNodes", len(nodes))),
                         params))
    log(f"  {len(nodes)} fragmented nodes, {len(measured)} gang pods, "
        f"drain prefixes capped at {max_nodes}")

    def _plan():
        cands = gang_consolidation_candidates(nodes, warm,
                                              max_nodes=max_nodes)
        return plan_gang_defrag(nodes, warm, measured, "bench", cands)

    # warmup excluded (JIT compile of the filter program), as everywhere
    t0 = time.time()
    _plan()
    compile_s = time.time() - t0
    t0 = time.time()
    plan = _plan()
    plan_s = time.time() - t0

    seated = len(plan.gang_moves)
    thresholds = workload.get("thresholds") or {}
    passed = seated >= len(measured)
    if "DefragPlanSeconds" in thresholds:
        passed = passed and plan_s <= thresholds["DefragPlanSeconds"]
    return {
        "case": case["name"], "workload": workload["name"],
        "DefragPlanSeconds": round(plan_s, 4),
        "compile_s": round(compile_s, 2),
        "batch_victims": plan.batch_victims,
        "candidate_sets": plan.batch_sets,
        "evictions": plan.evictions,
        "gang_seated": seated, "pods": len(measured), "nodes": len(nodes),
        "thresholds": thresholds, "passed": passed,
    }


def _run_churn_workload(case: dict, workload: dict, params: dict,
                        churn_op: dict, log, scale: float = 1.0,
                        batch: int = 512) -> dict:
    """The ``churn`` opcode (upstream scheduler_perf's API-churn op): churn
    is an INTEGRATION-level behavior — nodes and unrelated pods recycling
    through the API while the measured pods schedule — so it runs through
    the CONNECTED harness (live apiserver + informers + the resident drain
    context's invalidate-and-rebuild path), not the raw device drain.
    Reference: test/integration/scheduler_perf/scheduler_perf.go
    (churnOp, Recreate mode)."""
    from benchmarks.connected import run_connected
    mode = churn_op.get("mode", "recreate")
    if mode != "recreate":
        raise ValueError(f"churn mode {mode!r} not implemented "
                         "(only 'recreate')")
    res = run_connected(
        n_pods=int(params["measurePods"]), n_nodes=int(params["initNodes"]),
        batch_size=min(batch, 512), churn=True,
        churn_period_s=float(churn_op.get("intervalMilliseconds", 100))
        / 1000.0,
        log=log)
    thresholds = workload.get("thresholds") or {}
    throughput = res["SchedulingThroughput"]
    passed = (res["bound"] >= res["pods"]
              and all(throughput >= t * scale
                      for k, t in thresholds.items()
                      if k == "SchedulingThroughput"))
    # HARD SLO gates (distinct from the advisory thresholds above): a
    # missing or regressed p99/throughput figure must fail the bench run,
    # not read as fine — the result carries them as slo_failures.
    # Throughput floors scale with the workload like the advisory
    # thresholds do; latency ceilings stay absolute (a scaled-down run is
    # only ever faster).
    from benchmarks.connected import check_slo_gates
    slo = {k: (v * scale if k == "SchedulingThroughput" else v)
           for k, v in (workload.get("sloGates") or {}).items()}
    slo_failures = check_slo_gates(res, slo)
    return {
        "case": case["name"], "workload": workload["name"],
        "SchedulingThroughput": throughput,
        "p99_attempt_latency_s": res.get("p99_attempt_latency_s"),
        "p99_schedule_latency_s": res.get("p99_attempt_latency_s"),
        "scheduled": res["bound"], "pods": res["pods"],
        "nodes": res["nodes"], "measure_s": res["measure_s"],
        "churn_api_ops": res.get("churn_api_ops", 0),
        "ctx_stats": res.get("ctx_stats"),
        "connected": True,
        "thresholds": thresholds, "passed": passed and not slo_failures,
        "slo_gates": slo, "slo_failures": slo_failures,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--labels", default=None,
                    help="only workloads carrying this label (e.g. short)")
    ap.add_argument("--case", default=None, help="only this test case")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="scale all counts (0.1 = 10%% size)")
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--config", default=None)
    args = ap.parse_args(argv)

    cases = load_config(args.config)
    failed = 0
    for case in cases:
        if args.case and case["name"] != args.case:
            continue
        for workload in case["workloads"]:
            if args.labels and args.labels not in (workload.get("labels") or []):
                continue
            res = run_workload(case, workload, scale=args.scale,
                               batch=args.batch,
                               log=lambda *a: print(*a, file=sys.stderr))
            print(json.dumps(res))
            if not res["passed"]:
                failed += 1
    return 1 if failed else 0


if __name__ == "__main__":
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    raise SystemExit(main())
