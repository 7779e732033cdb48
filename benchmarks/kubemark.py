"""Kubemark-scale e2e: hundreds of hollow kubelets + the connected
scheduler against the separate-process apiserver.

Reference: ``pkg/kubemark`` + sig-scalability's 5k-node control-plane
tests: real node-agent code over a mocked CRI exercising the WHOLE loop —
node registration and heartbeats through the API, the scheduler binding
through its informers, kubelets observing their bindings over the shared
watch and driving pods to Running with status writes the scheduler's cache
then confirms. Measures pods-to-Running throughput and heartbeat-fleet
health under that load.
"""

from __future__ import annotations

import multiprocessing as mp
import time


def run_kubemark(n_hollow: int = 500, n_pods: int = 1000,
                 heartbeat_period: float = 10.0, timeout: float = 240.0,
                 log=lambda *a: None) -> dict:
    from benchmarks.connected import _serve, _span_totals, _trace_window
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.kubelet.kubemark import HollowCluster
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.testing.wrappers import make_pod
    from kubernetes_tpu.utils.tracing import TRACER

    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe()
    server = ctx.Process(target=_serve, args=(child,), daemon=True)
    server.start()
    port = parent.recv()
    url = f"http://127.0.0.1:{port}"
    cluster = runner = None
    try:
        # span the whole run (register -> bind -> Running) the way the
        # connected bench is spanned, so a BENCH file shows where the
        # seconds go: registration, scheduler sync, status writes (batched
        # flushes appear as kubemark/status_flush), heartbeats
        _trace_window()
        t0 = time.time()
        with TRACER.span("kubemark/register", nodes=n_hollow):
            cluster = HollowCluster(HTTPClient(url, timeout=60.0), n_hollow,
                                    heartbeat_period=heartbeat_period).start()
        t_reg = time.time() - t0
        log(f"  {n_hollow} hollow nodes registered in {t_reg:.1f}s")

        with TRACER.span("kubemark/scheduler_sync"):
            runner = SchedulerRunner(
                HTTPClient(url), SchedulerConfiguration(batch_size=256,
                                                        max_drain_batches=2))
            runner.start(wait_sync=60.0)

        client = HTTPClient(url, timeout=60.0)
        pods = [make_pod(f"km-{i}", "default")
                .req({"cpu": "100m", "memory": "64Mi"}).obj().to_dict()
                for i in range(n_pods)]
        t_start = time.time()
        with TRACER.span("kubemark/create_pods", pods=n_pods):
            client.pods("default").create_many(pods)
        deadline = t_start + timeout
        bound = running = 0
        milestones: dict = {}  # phase -> seconds since t_start
        while time.time() < deadline:
            listed = client.pods("default").list()
            bound = sum(1 for p in listed if p["spec"].get("nodeName"))
            running = sum(1 for p in listed
                          if (p.get("status") or {}).get("phase")
                          == "Running")
            if bound >= n_pods and "all_bound" not in milestones:
                milestones["all_bound"] = round(time.time() - t_start, 2)
            for frac, key in ((0.5, "half_running"), (1.0, "all_running")):
                if running >= n_pods * frac and key not in milestones:
                    milestones[key] = round(time.time() - t_start, 2)
            if running >= n_pods:
                break
            time.sleep(0.5)
        dt = time.time() - t_start
        # fleet health: Ready heartbeats landing under load
        ready = sum(
            1 for n in client.nodes().list()
            if any(c.get("type") == "Ready" and c.get("status") == "True"
                   for c in (n.get("status") or {}).get("conditions") or []))
        log(f"  {bound} bound, {running} running at +{dt:.1f}s; "
            f"{ready}/{n_hollow} nodes Ready")
        return {
            "case": "Kubemark",
            "workload": f"{n_pods}pods_{n_hollow}hollow",
            "hollow_nodes": n_hollow, "pods": n_pods,
            "register_s": round(t_reg, 1),
            "bound": bound, "running": running,
            "RunningThroughput": round(running / dt, 1) if dt > 0 else 0.0,
            "measure_s": round(dt, 2),
            "nodes_ready": ready,
            "milestones": milestones,
            "span_ms": _span_totals(),
        }
    finally:
        try:
            if runner is not None:
                runner.stop()
            if cluster is not None:
                cluster.stop()
        except Exception:
            pass
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
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    res = run_kubemark(
        n_hollow=int(os.environ.get("BENCH_KUBEMARK_NODES", "500")),
        n_pods=int(os.environ.get("BENCH_KUBEMARK_PODS", "1000")),
        log=lambda *a: print(*a, file=sys.stderr))
    print(json.dumps(res))
