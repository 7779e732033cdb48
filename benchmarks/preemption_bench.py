"""Preemption benchmark: victim search throughput at fleet scale.

The reference's preemption hot path is ``DryRunPreemption``
(``pkg/scheduler/framework/preemption/preemption.go``): per failed pod,
simulate victim eviction on every candidate node (16 goroutines). Here the
whole WAVE of preemptors runs as one [Q,N,V+1] sequential-commit scan
(ops/preemption.py ``_wave_scan``) with each proposal exactly verified
host-side against a shared oracle — this measures end-to-end
``preempt_wave`` throughput (preemptors/second) on a saturated cluster, vs
the pure-host serial scan on a sample for the speedup ratio.

Scenario: every node is full of low-priority pods; a wave of high-priority
pods arrives, each needing victims. Each preemptor's chosen victims are
evicted from the bound set before the next (sequential cluster mutation,
like the real failure path).
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_saturated(n_nodes: int, pods_per_node: int = 2):
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    nodes = [make_node(f"n{i}").capacity(
        {"cpu": "8", "memory": "32Gi", "pods": "32"}).obj()
        for i in range(n_nodes)]
    bound = []
    for i in range(n_nodes):
        for j in range(pods_per_node):
            bound.append(
                make_pod(f"low-{i}-{j}")
                .req({"cpu": "4", "memory": "4Gi"})
                .priority(1 + (i + j) % 5).node(f"n{i}").obj())
    return nodes, bound


def run_preemption(n_nodes: int = 5000, n_preemptors: int = 256,
                   host_sample: int = 8, log=lambda *a: None) -> dict:
    from kubernetes_tpu.sched.preemption import find_candidate, preempt_wave
    from kubernetes_tpu.testing.wrappers import make_pod

    nodes, bound = build_saturated(n_nodes)
    preemptors = [make_pod(f"hi-{k}").req({"cpu": "6", "memory": "8Gi"})
                  .priority(100).obj() for k in range(n_preemptors)]
    log(f"  {n_nodes} nodes saturated with {len(bound)} low-priority pods")

    # warmup: compile the wave scan + static-mask filters at this shape
    # (the wave mutates nothing — inputs are re-encoded per call)
    preempt_wave(nodes, bound, preemptors)

    t0 = time.time()
    results = preempt_wave(nodes, bound, preemptors)
    resolved = sum(r is not None for r in results)
    dt = time.time() - t0
    tensor_rate = resolved / dt if dt > 0 else 0.0

    # host-serial comparison on a small sample (the full sweep would take
    # minutes at fleet scale — that is the point)
    t0 = time.time()
    for pod in preemptors[:host_sample]:
        find_candidate(nodes, bound, pod)
    host_dt = time.time() - t0
    host_rate = host_sample / host_dt if host_dt > 0 else 0.0

    return {
        "case": "Preemption", "workload": f"{n_preemptors}x{n_nodes}",
        "PreemptionThroughput": round(tensor_rate, 1),
        "resolved": resolved, "preemptors": n_preemptors, "nodes": n_nodes,
        "measure_s": round(dt, 2),
        "host_serial_per_sec": round(host_rate, 2),
        "speedup_vs_host": (round(tensor_rate / host_rate, 1)
                            if host_rate else None),
    }


if __name__ == "__main__":
    import json
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    res = run_preemption(
        n_nodes=int(os.environ.get("BENCH_PREEMPT_NODES", "5000")),
        n_preemptors=int(os.environ.get("BENCH_PREEMPT_PODS", "256")),
        log=lambda *a: print(*a, file=sys.stderr))
    print(json.dumps(res))
