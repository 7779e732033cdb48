"""ScaleFleet: a two-point hollow-fleet sweep proving the control plane
is SUBLINEAR in fleet size.

PR 11 made the device program effectively free; what remains of a
ConnectedMesh leg at fleet scale is the hollow fleet's own control-plane
traffic — heartbeats, node leases, pod status. This case registers a
hollow fleet at two sizes (default sized to the box; the 100k campaign
tier runs ``BENCH_SCALE_NODES="1250 10000"``), drives sustained churn
through the one resident scheduler program, and measures the combined
``kubelet/heartbeat`` + ``kubelet/lease_renew`` + ``kubemark/status_flush``
span time over an identical steady-state window at each size.

Hard gate (the PR-8 SLO discipline): with the bulk fan-in paths
(``nodes/-/status``, ``leases/-/renew``, sharded batchers) the combined
control-plane span must grow <= ``max_growth`` (default 2x) while the
fleet grows ``fleet_sizes[-1]/fleet_sizes[0]`` (default 8x) — and a
MISSING span is a failure, never a silent pass. The fail-fast invariant
auditor is live for every leg.
"""

from __future__ import annotations

import multiprocessing as mp
import time
import urllib.request

# the control-plane spans the sublinear gate sums (missing = failure)
CONTROL_PLANE_SPANS = ("kubelet/heartbeat", "kubelet/lease_renew",
                       "kubemark/status_flush")


def _bulk_request_counts(url: str) -> dict:
    """apiserver_bulk_requests_total{endpoint=...} from the apiserver
    subprocess's /metrics — attributes how much of the leg's fan-in rode
    bulk endpoints (the store-side counter lives in the server process)."""
    out: dict = {}
    try:
        with urllib.request.urlopen(url + "/metrics", timeout=10.0) as resp:
            for line in resp.read().decode().splitlines():
                if line.startswith("apiserver_bulk_requests_total{"):
                    label, _, val = line.rpartition(" ")
                    ep = label.split('endpoint="', 1)[-1].split('"')[0]
                    out[ep] = float(val)
    except Exception:
        pass  # metrics are attribution, not the gate
    return out


def _pod_churn_loop(client, stop, period_s: float = 0.1,
                    counter=None) -> None:
    """Sustained POD churn (namespace ``churn``): create/delete a rolling
    window of short-lived pods the scheduler binds onto the hollow fleet
    and the kubelets drive to Running (status traffic). Deliberately NO
    node churn: pod deltas ride the one resident scheduler program as
    fused folds, while a node add/delete forces a full O(fleet) cluster
    re-encode per op — that is the scheduler's scaling story, and letting
    it peg the GIL here would charge its cost to the control-plane spans
    this case gates on."""
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
            if len(live) > 3:
                client.pods("churn").delete(live.pop(0))
            if counter is not None:
                counter["ops"] = counter.get("ops", 0) + 2
        except Exception:
            pass  # churn is background noise; the bench owns correctness
        stop.wait(period_s)


def _run_leg(n_hollow: int, n_pods: int, batch_size: int,
             heartbeat_period: float, window_s: float, n_windows: int,
             churn_period_s: float, timeout: float, log) -> dict:
    import threading

    from benchmarks.connected import (_audit_close, _bench_auditor,
                                      _serve, _span_totals, _trace_window)
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.kubelet.kubemark import HollowCluster
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.testing.wrappers import make_pod

    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe()
    server = ctx.Process(target=_serve, args=(child,), daemon=True)
    server.start()
    port = parent.recv()
    url = f"http://127.0.0.1:{port}"
    cluster = runner = None
    leg: dict = {"nodes": n_hollow, "pods": n_pods}
    try:
        t0 = time.time()
        cluster = HollowCluster(HTTPClient(url, timeout=120.0), n_hollow,
                                prefix=f"sf{n_hollow}",
                                heartbeat_period=heartbeat_period
                                ).start(wait_sync=60.0)
        leg["register_s"] = round(time.time() - t0, 2)
        log(f"  {n_hollow} hollow nodes registered in "
            f"{leg['register_s']}s")

        runner = SchedulerRunner(
            HTTPClient(url),
            SchedulerConfiguration(batch_size=batch_size,
                                   max_drain_batches=2))
        runner.auditor = _bench_auditor(runner, HTTPClient(url))
        runner.start(wait_sync=60.0)

        client = HTTPClient(url, timeout=120.0)
        pods = [make_pod(f"sf-{i}", "default")
                .req({"cpu": "100m", "memory": "64Mi"}).obj().to_dict()
                for i in range(n_pods)]
        t_bind = time.time()
        client.pods("default").create_many(pods)
        deadline = t_bind + timeout
        bound = 0
        while time.time() < deadline:
            bound = sum(1 for p in client.pods("default").list()
                        if p["spec"].get("nodeName"))
            if bound >= n_pods:
                break
            time.sleep(0.5)
        leg["bound"] = bound
        leg["bind_s"] = round(time.time() - t_bind, 2)
        log(f"  {bound}/{n_pods} bound at +{leg['bind_s']}s")

        # steady state: identical wall-clock window at every fleet size —
        # the churn load is size-INDEPENDENT, so whatever grows between
        # legs is the fleet's own control-plane traffic. Churn warms up
        # BEFORE the window opens: the first churn nodes/pods grow encode
        # buckets and trigger the leg's last JIT recompiles, which must
        # not be charged to either leg's measured spans.
        churn_stop = threading.Event()
        churn_stats: dict = {}
        threading.Thread(target=_pod_churn_loop,
                         args=(HTTPClient(url), churn_stop),
                         kwargs={"counter": churn_stats,
                                 "period_s": churn_period_s},
                         daemon=True).start()
        time.sleep(6.0)  # churn warm-up (outside the measured window)
        churn_stats["ops"] = 0
        # min-of-K windows: the spans are WALL time in a process whose one
        # core also runs the scheduler's device program, so a flush that
        # lands while a dispatch holds the GIL reads 2-3x its true cost.
        # That contamination is strictly ADDITIVE, so the minimum across
        # identical consecutive windows is the honest estimator of what
        # the control plane itself costs (the timeit-min discipline).
        windows: list[dict] = []
        for _ in range(n_windows):
            _trace_window()
            time.sleep(window_s)
            windows.append(_span_totals())
        spans = windows[-1]
        churn_stop.set()
        leg["window_s"] = window_s
        leg["windows"] = [{k: w.get(k) for k in CONTROL_PLANE_SPANS}
                          for w in windows]
        leg["span_ms"] = spans
        cp: dict = {}
        for k in CONTROL_PLANE_SPANS:
            seen = [w.get(k) for w in windows
                    if isinstance(w.get(k), (int, float)) and w.get(k) > 0]
            cp[k] = min(seen) if seen else None  # absent everywhere = None
        leg["control_plane_ms"] = cp
        leg["churn_api_ops"] = churn_stats.get("ops", 0)
        leg["fleet"] = cluster.fleet_stats()
        leg["bulk_requests"] = _bulk_request_counts(url)
        leg.update(_audit_close(runner))
        return leg
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


def run_scale_fleet(fleet_sizes=(256, 2048), n_pods: int = 256,
                    batch_size: int = 256, heartbeat_period: float = 5.0,
                    window_s: float = 12.0, n_windows: int = 3,
                    churn_period_s: float = 0.5,
                    max_growth: float = 2.0, timeout: float = 240.0,
                    log=lambda *a: None) -> dict:
    sizes = sorted(int(s) for s in fleet_sizes)
    legs = []
    for n in sizes:
        log(f"  ScaleFleet leg: {n} hollow nodes ...")
        legs.append(_run_leg(n, n_pods, batch_size, heartbeat_period,
                             window_s, n_windows, churn_period_s,
                             timeout, log))

    result: dict = {
        "case": "ScaleFleet",
        "workload": "x".join(str(n) for n in sizes)
                    + f"hollow_{n_pods}pods",
        "fleet_sizes": sizes,
        "heartbeat_period_s": heartbeat_period,
        "window_s": window_s,
        "windows_per_leg": n_windows,
        "max_growth": max_growth,
        "legs": legs,
        "invariant_violations": sum(
            int(leg.get("invariant_violations") or 0) for leg in legs),
    }

    # ---- the sublinear gate (missing number = failure) -------------------
    failures: list[str] = []
    totals = []
    for leg in legs:
        total = 0.0
        for k in CONTROL_PLANE_SPANS:
            v = (leg.get("control_plane_ms") or {}).get(k)
            if not isinstance(v, (int, float)):
                failures.append(
                    f"{leg['nodes']}-node leg: span {k!r} missing — the "
                    "gate cannot pass silently")
                v = 0.0
            total += v
        totals.append(round(total, 1))
        if leg.get("bound", 0) < n_pods:
            failures.append(f"{leg['nodes']}-node leg: only "
                            f"{leg.get('bound', 0)}/{n_pods} pods bound")
    result["control_plane_totals_ms"] = dict(zip(
        (str(n) for n in sizes), totals))
    if len(sizes) < 2:
        # a one-leg "sweep" has no growth factor — and a silently absent
        # figure must never read as a pass (the BENCH_r05 lesson)
        failures.append(
            f"fleet sweep needs >= 2 sizes to gate growth (got {sizes})")
    if len(totals) >= 2 and not failures:
        small, big = totals[0], totals[-1]
        if small <= 0:
            failures.append("smallest leg recorded 0 control-plane span "
                            "ms — nothing measured, refusing to pass")
        else:
            growth = round(big / small, 3)
            result["growth_factor"] = growth
            result["size_growth"] = round(sizes[-1] / sizes[0], 2)
            if growth > max_growth:
                failures.append(
                    f"control-plane span grew {growth}x for a "
                    f"{result['size_growth']}x fleet (gate {max_growth}x)")
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
    sizes = [int(t) for t in os.environ.get(
        "BENCH_SCALE_NODES", "256 2048").replace(",", " ").split()]
    res = run_scale_fleet(
        fleet_sizes=sizes,
        n_pods=int(os.environ.get("BENCH_SCALE_PODS", "256")),
        window_s=float(os.environ.get("BENCH_SCALE_WINDOW_S", "12")),
        heartbeat_period=float(os.environ.get("BENCH_SCALE_HB_PERIOD",
                                              "5.0")),
        max_growth=float(os.environ.get("BENCH_SCALE_MAX_GROWTH", "2.0")),
        log=lambda *a: print(*a, file=sys.stderr))
    print(json.dumps(res))
    if res.get("slo_failures") or res.get("invariant_violations"):
        sys.exit(1)
