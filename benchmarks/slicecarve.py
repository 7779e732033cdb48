"""SliceCarve: sustained contiguous-slice churn through the carve path.

One apiserver + one connected scheduler over a labeled ICI torus
(``kubernetes-tpu.io/topology-{x,y,z}`` node labels); a few cells are
pinned near-full so every carve must route around fragmentation. The
window submits slice gangs (``kubernetes-tpu.io/slice-shape``) back to
back: each gang must land on one CONTIGUOUS torus box, bind fully, and
clear before the next.

Hard gates (missing number = failure, PR-8 discipline):
  - every carved gang occupies a contiguous box of the requested shape
    (topology/slicing.is_contiguous_slice over the bound API state),
  - 0 invariant violations (fail-fast auditor live, slice_contiguity
    included),
  - ZERO XLA compiles in the steady window — the carve's (dims, rots)
    static args are fixed per installed topology, so steady-state carves
    ride one warm program,
  - the ParitySentinel's carve site (armed at every=1) confirms every
    device carve against the numpy oracle carver: 0 divergences.
"""

from __future__ import annotations

import time


def run_slice_carve(grid: str = "4x4x2", shape: str = "2x2x2",
                    node_cpu: str = "8", member_cpu: str = "2",
                    n_fragment: int = 4, window_s: float = 10.0,
                    carve_timeout_s: float = 30.0,
                    log=lambda *a: None) -> dict:
    from benchmarks.connected import _audit_close, _bench_auditor
    from benchmarks.fleetchurn import _CompileCounter, _p99
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.sched.runner import SchedulerRunner
    from kubernetes_tpu.store.apiserver import APIServer
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    from kubernetes_tpu.topology.slicing import (GANG_LABEL,
                                                 SLICE_SHAPE_LABEL,
                                                 coords_of_labels,
                                                 is_contiguous_slice,
                                                 parse_shape,
                                                 topology_labels)

    dims = parse_shape(grid)
    shp = parse_shape(shape)
    want = shp[0] * shp[1] * shp[2]
    server = None
    runner = None
    failures: list[str] = []
    result: dict = {"case": "SliceCarve",
                    "workload": f"{grid}grid_{shape}slices_"
                                f"frag{n_fragment}",
                    "grid": grid, "shape": shape, "window_s": window_s}
    try:
        server = APIServer().start()
        client = HTTPClient(server.url, timeout=60.0)
        cells = [(x, y, z) for x in range(dims[0]) for y in range(dims[1])
                 for z in range(dims[2])]
        for x, y, z in cells:
            nb = make_node(f"tn-{x}-{y}-{z}").capacity(
                {"cpu": node_cpu, "memory": "16Gi", "pods": "32"})
            for k, v in topology_labels(x, y, z).items():
                nb = nb.label(k, v)
            client.nodes().create(nb.obj().to_dict())
        # fragment: pin near-full pods on spread-out cells so those cells
        # can never host a member — every carve must route around them
        frag_cells = cells[:: max(1, len(cells) // max(1, n_fragment))][
            :n_fragment]
        frag = int(node_cpu) * 1000 - 500  # 500m headroom: under member_cpu
        for i, (x, y, z) in enumerate(frag_cells):
            client.pods("default").create(
                make_pod(f"frag-{i}").req({"cpu": f"{frag}m"})
                .node(f"tn-{x}-{y}-{z}").obj().to_dict())
        result["nodes"] = len(cells)
        result["fragmented_cells"] = len(frag_cells)

        runner = SchedulerRunner(client, SchedulerConfiguration(
            batch_size=max(8, want), backoff_initial_s=0.05,
            backoff_max_s=0.2))
        runner.auditor = _bench_auditor(runner, HTTPClient(server.url))
        runner.start(wait_sync=30.0)
        runner.scheduler.sentinel.every = 1  # judge EVERY carve
        node_coords = {f"tn-{x}-{y}-{z}": (x, y, z) for x, y, z in cells}

        def run_gang(gid: str) -> tuple:
            """Submit one slice gang, wait for full bind -> (bind seconds
            or None, placements). Deletes the gang's pods afterwards."""
            names = [f"{gid}-{m}" for m in range(want)]
            t0 = time.time()
            client.pods("default").create_many(
                [make_pod(n).req({"cpu": member_cpu})
                 .labels({GANG_LABEL: gid, SLICE_SHAPE_LABEL: shape})
                 .obj().to_dict() for n in names])
            placed: dict = {}
            deadline = t0 + carve_timeout_s
            while time.time() < deadline and len(placed) < want:
                for p in client.pods("default").list():
                    nm = p["metadata"]["name"]
                    if nm in names and (p.get("spec") or {}).get("nodeName"):
                        placed[nm] = p["spec"]["nodeName"]
                time.sleep(0.05)
            took = (time.time() - t0) if len(placed) == want else None
            for n in names:
                try:
                    client.pods("default").delete(n)
                except Exception:
                    pass
            return took, placed

        # ---- warm leg: compile the carve + group-path programs at the
        # window's exact static args (dims, rots, buckets) ----------------
        compiles = _CompileCounter()
        took, placed = run_gang("warm")
        if took is None:
            failures.append(f"warm gang never fully bound "
                            f"({len(placed)}/{want})")
        result["warmup_quiet_s"] = round(
            compiles.wait_quiet(quiet_s=3.0, timeout_s=45.0), 1)

        # ---- steady window: back-to-back carves, zero compiles -----------
        compiles.arm()
        t_win = time.time()
        carves = 0
        contiguous_ok = 0
        lat: list[float] = []
        while time.time() - t_win < window_s:
            gid = f"g{carves}"
            took, placed = run_gang(gid)
            if took is None:
                failures.append(f"gang {gid}: only {len(placed)}/{want} "
                                f"members bound within {carve_timeout_s}s")
                break
            lat.append(took)
            carves += 1
            coords = [node_coords.get(nn) for nn in placed.values()]
            if (all(c is not None for c in coords)
                    and is_contiguous_slice(coords, shp, dims)):
                contiguous_ok += 1
            else:
                failures.append(f"gang {gid}: members NOT on a contiguous "
                                f"{shape} box: {sorted(placed.items())}")
        xla_compiles = compiles.disarm()
        result["carves"] = carves
        result["contiguous_ok"] = contiguous_ok
        result["carves_per_s"] = round(carves / window_s, 2)
        result["p99_carve_bind_s"] = _p99(lat)
        result["ctx_window"] = {"xla_compiles": xla_compiles}
        if carves <= 0:
            failures.append("no carve completed in the window — the gate "
                            "cannot pass silently")
        if xla_compiles != 0:
            failures.append(f"one-warm-program violated: {xla_compiles} "
                            "XLA compile(s) during the steady window")

        status = runner.scheduler.topology_status()
        result["topology"] = status
        if status is None:
            failures.append("topology status missing: the scheduler saw "
                            "no coordinates")
        result.update(_audit_close(runner))
        if result.get("invariant_violations") is None:
            failures.append("invariant_violations missing")
        parity = result.get("parity") or {}
        if parity.get("samples", {}).get("carve", 0) < carves:
            failures.append(
                f"sentinel carve site sampled "
                f"{parity.get('samples', {}).get('carve', 0)} of {carves} "
                "carves at every=1")
        if parity.get("divergences"):
            failures.append(f"{parity['divergences']} carve parity "
                            "divergence(s) — device/oracle split")
    finally:
        try:
            if runner is not None:
                runner.stop()
        except Exception:
            pass
        try:
            if server is not None:
                server.stop()
        except Exception:
            pass
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
    res = run_slice_carve(
        grid=os.environ.get("BENCH_SLICE_GRID", "4x4x2"),
        shape=os.environ.get("BENCH_SLICE_SHAPE", "2x2x2"),
        window_s=float(os.environ.get("BENCH_SLICE_WINDOW_S", "10")),
        n_fragment=int(os.environ.get("BENCH_SLICE_FRAG", "4")),
        log=lambda *a: print(*a, file=sys.stderr))
    print(json.dumps(res))
    if res.get("slo_failures") or res.get("invariant_violations"):
        sys.exit(1)
