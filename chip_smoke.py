#!/usr/bin/env python3
"""chip_smoke.py — the served scheduling path, once, on the chip.

    python chip_smoke.py                 one chip (what the driver runs)
    python chip_smoke.py --mesh 1x4      the four-chip host, one process
    python chip_smoke.py --rehearsal     CPU, tiny sizes, never passes

The quickest proof that the system still starts on a TPU and that the
DEVICE does the scheduling. The product is built to keep going when a
device program fails (mesh -> single device -> numpy oracle), so "every
pod bound" proves nothing by itself: this script reads the counters the
product keeps and fails unless the answers came from the resident device
program and the parity sentinel refuted none of them.

What runs, in order, each chip-owning phase in a process of its own (this
parent never imports jax, so it never holds a chip):

  served   benchmarks/connected.run_connected — apiserver process,
           SchedulerRunner (informers -> queue -> encode/stage ->
           drain_step on the resident context -> resolver -> bulk bind),
           watcher process — 10,000 MixedHeterogeneous pods on 5,000
           nodes, batch 512 x 2 drains, product defaults,
           paritySampleEvery: 1 so the numpy oracle judges every drain.
           Then, in the same process, one dispatch each of the device
           programs that burst does not reach, each against the numpy
           reference the repo has for it; every other jitted entry point
           under kubernetes_tpu/ is named under ``not_exercised``.
  warm     a second process boots against an identical freshly seeded
           cluster and runs only the warm ladder: with the compile cache
           where parallel/aot.place_compile_cache put it
           (JAX_COMPILATION_CACHE_DIR, else one fixed directory in the
           checkout) every program must load, none compile.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
and the exit code 0 — only on a TPU, only if every gate held. Anything
else exits non-zero and prints no such line. The full report lands in
chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import signal
import sys
import time
import traceback

# Nothing at module level may touch a jax backend: spawn children (the
# phases here, the apiserver and watcher under run_connected) re-import
# this file as their __main__.

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# The size is fixed: no argument changes it, so a pass is always a pass at
# this width. The whole command takes ~140 s of the contract's 1200 s on
# one v5e, so nothing is cut; should a cut ever be forced, nodes stay at
# 5,000, FULL["pods"] changes here and REDUCED says from what and why.
FULL = {"nodes": 5000, "pods": 10000, "batch": 512, "preemptors": 128}
REDUCED: list = []
TINY = {"nodes": 48, "pods": 192, "batch": 32, "preemptors": 8}  # rehearsal
DRAIN_BATCHES = 2  # maxDrainBatches of both listed cells (yardstick/configs)

EXIT_FAILED = 1
EXIT_NO_PROGRAM = 4     # chip_smoke.py alone, without the repo
EXIT_REHEARSAL = 10     # the rehearsal's gates held; still not a pass

# the contract allows 1200 s in all, compilation included
SERVED_TIMEOUT_S = 840.0
WARM_TIMEOUT_S = 240.0

# Every jitted entry point under kubernetes_tpu/, by "<file>:<name>", and
# what this script does about it. _declared_vs_found() fails the run when
# the tree grows one that is not listed here: silence is not allowed.
EXERCISED = {
    "models/gang.py:drain_step": "served path, every drain sentinel-judged",
    "models/gang.py:apply_ctx_patch":
        "warm ladder only (empty patch); runs, result unchecked",
    "models/gang.py:_stage": "stages the resident context (one device)",
    "models/gang.py:gang_converge": "gang_schedule, group-path shapes",
    "ops/preemption.py:_wave_scan": "preempt_wave, 128 preemptors",
    "ops/preemption.py:_dry_run": "find_candidate_tensor, one preemptor",
    "sched/preemption.py:_STATIC_FILTERS_JIT": "tensor_static_masks",
    "models/explain.py:explain_step": "SchedulingExplainer, tensor judge",
    "topology/carve.py:carve_step": "carve_device, 2x2x2 on a 4x4x4 torus",
    "encode/overlay.py:_plan_mask_program": "ResidentPlanner.mask_scores",
    "encode/overlay.py:_overlay_mask_program":
        "ResidentPlanner.overlay_mask",
    "encode/overlay.py:_quota_program": "tenant_quota_mask",
}
NOT_EXERCISED = {
    "models/schedule_step.py:schedule_step":
        "not on the served path (extender server, KTPU_CHECK, "
        "__graft_entry__); ROADMAP D1 folds it into drain_step",
    "models/gang.py:gang_round":
        "single propose/accept round kept for tests; the product runs "
        "gang_converge",
    "models/gang.py:_gang_drain_compiled":
        "raw gang_drain, benchmarks/scheduler_perf.py only; ROADMAP D1",
    "encode/overlay.py:_overlay_ct_program":
        "resident with_hypothetical for the autoscaler's binpack; no "
        "numpy reference at this level (tests/test_planner.py pins "
        "resident-vs-cold plans on CPU)",
    "encode/overlay.py:_without_program":
        "resident without_pods for the descheduler; same reason",
    "utils/sanity.py:checked":
        "KTPU_CHECK debug re-evaluation of schedule_step, off by default",
}


# ---- output ----------------------------------------------------------------

def _prefix(opts: dict) -> str:
    return "REHEARSAL platform=cpu " if opts["rehearsal"] else ""


def say(opts: dict, msg: str) -> None:
    for line in str(msg).splitlines() or [""]:
        print(_prefix(opts) + line, flush=True)


def _short(obj, limit: int = 1500) -> str:
    text = json.dumps(obj, default=str)
    return text if len(text) <= limit else text[:limit] + " ...}"


# ---- the tree's jitted entry points ------------------------------------------

def jit_sites(root: str) -> dict:
    """{"<file>:<name>": line} for every jax.jit in the package, by AST:
    decorators (bare or through functools.partial) and jit(...) calls
    bound to a name or made inside a function."""
    import ast

    def is_jit(node) -> bool:
        return (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax")

    def partial_jit(node) -> bool:
        return (isinstance(node, ast.Call)
                and getattr(node.func, "id",
                            getattr(node.func, "attr", "")) == "partial"
                and any(is_jit(a) for a in node.args))

    def jit_call(node) -> bool:
        """jax.jit(f, ...) or partial(jax.jit, ...)(f)."""
        return isinstance(node, ast.Call) and (
            is_jit(node.func) or partial_jit(node.func))

    found: dict = {}
    for dirpath, _dirs, files in os.walk(root):
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            rel = os.path.relpath(path, root)
            with open(path) as fh:
                tree = ast.parse(fh.read())
            parents = {c: p for p in ast.walk(tree)
                       for c in ast.iter_child_nodes(p)}
            for node in ast.walk(tree):
                if isinstance(node, ast.FunctionDef):
                    if any(is_jit(d) or partial_jit(d) or jit_call(d)
                           for d in node.decorator_list):
                        found[f"{rel}:{node.name}"] = node.lineno
                elif jit_call(node):
                    up = parents.get(node)
                    if isinstance(up, ast.FunctionDef) \
                            and node in up.decorator_list:
                        continue  # a decorator: counted above
                    while up is not None and not isinstance(
                            up, (ast.Assign, ast.FunctionDef)):
                        up = parents.get(up)
                    if isinstance(up, ast.Assign):
                        name = getattr(up.targets[0], "id", None) \
                            or getattr(up.targets[0], "attr", "?")
                    else:
                        name = up.name if up is not None else "?"
                    found[f"{rel}:{name}"] = node.lineno
    return found


def _declared_vs_found() -> list[str]:
    found = jit_sites(os.path.join(HERE, "kubernetes_tpu"))
    declared = set(EXERCISED) | set(NOT_EXERCISED)
    # ktpu-lint's donation rule names jax.jit in strings only
    return ([f"jitted entry point {k} (line {found[k]}) is neither "
             "exercised nor named under not_exercised"
             for k in sorted(set(found) - declared)]
            + [f"{k} is declared here but no longer in the tree"
               for k in sorted(declared - set(found))])


# ---- small device programs, each against its numpy reference -----------------

def _error_logs():
    """ERROR records from the product's loggers while a block runs: the
    fallbacks inside preempt_wave and friends log and carry on."""
    import logging
    from benchmarks.connected import captured_logs
    return captured_logs("kubernetes_tpu", logging.ERROR)


def _log_lines(records: list) -> list:
    return [f"{r.name}: {r.getMessage()}"[:300] for r in records]


def check_preemption(n_nodes: int, n_high: int) -> dict:
    """The run_connected_preemption shape: a saturated cluster, a wave of
    high-priority pods. Static masks against the host helper, the wave
    through preempt_wave (device scan + exact host verification) against
    the sentinel's oracle judge, one preemptor through the single-pod
    dry-run against the serial host scan."""
    import numpy as np
    from kubernetes_tpu.audit.sentinel import verify_wave_results
    from kubernetes_tpu.ops.preemption import _static_mask, dry_run_wave
    from kubernetes_tpu.sched import preemption as pmod
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    nodes = [make_node(f"n{i}").capacity(
        {"cpu": "8", "memory": "32Gi", "pods": "32"}).obj()
        for i in range(n_nodes)]
    bound = [make_pod(f"low-{i}-{j}", "default")
             .req({"cpu": "4", "memory": "4Gi"})
             .priority(1 + (i + j) % 5).node(f"n{i}").obj()
             for i in range(n_nodes) for j in range(2)]
    high = [make_pod(f"hi-{k}", "preempt").req({"cpu": "6", "memory": "8Gi"})
            .priority(100).obj() for k in range(n_high)]
    problems: list[str] = []
    masks = pmod.tensor_static_masks(nodes, high, bound_pods=bound,
                                     min_p=pmod.WAVE_BUCKET)
    ref = np.stack([_static_mask(nodes, p) for p in high[:2]])
    if masks.shape != (n_high, n_nodes) \
            or not np.array_equal(masks[:2], ref) \
            or not (masks == masks[0]).all():
        problems.append("static masks differ from the host helper")
    proposals = dry_run_wave(nodes, bound, high, [], static_masks=masks,
                             min_q=pmod.WAVE_BUCKET)
    proposed = sum(isinstance(p, tuple) for p in proposals)
    results = pmod.preempt_wave(nodes, bound, high, static_masks=masks,
                                min_q=pmod.WAVE_BUCKET)
    problems += verify_wave_results(nodes, bound, high, results)
    resolved = sum(r is not None for r in results)
    if proposed != n_high or resolved != n_high:
        problems.append(f"wave proposed {proposed} and resolved {resolved} "
                        f"of {n_high} preemptors")
    serial = pmod.find_candidate(nodes, bound, high[0])
    single = pmod.find_candidate_tensor(nodes, bound, high[0])

    def key(r):
        return r and (r.node_name, sorted(v.key for v in r.victims))
    if not (key(serial) == key(single) == key(results[0])):
        problems.append(f"first preemptor: serial scan {key(serial)}, "
                        f"single dry-run {key(single)}, wave "
                        f"{key(results[0])}")
    return {"problems": problems, "preemptors": n_high, "nodes": n_nodes,
            "resolved": resolved,
            "victims": sum(len(r.victims) for r in results if r)}


def _explain_vs_oracle(nodes: list, bound: list, pods: list) -> dict:
    """Pods through the product's explainer: the tensor judge's first-fail
    histogram (explain_step) against the oracle judge's."""
    from benchmarks.connected import _counter_deltas
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.metrics.registry import EXPLAIN_SAMPLES
    from kubernetes_tpu.sched.cache import SchedulerCache
    from kubernetes_tpu.sched.explainer import SchedulingExplainer
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    for p in bound:
        cache.add_pod(p)
    cfg = SchedulerConfiguration()
    ex = SchedulingExplainer(cfg, lambda: None)
    base = EXPLAIN_SAMPLES.items()
    try:
        ex.submit(cache, cfg.profiles[0], "single", pods)
        ex.drain(timeout=600.0)
        got = {p.key: ex.explain_of(p.key) or {} for p in pods}
        ref = ex._judge_oracle({"nodes": nodes, "bound": bound,
                                "ns_labels": cache.namespace_labels()}, pods)
    finally:
        ex.close()
    modes = _counter_deltas(EXPLAIN_SAMPLES, base)
    problems = []
    if modes != {"tensor": float(len(pods))}:
        problems.append(f"explain samples by mode: {modes} (the oracle "
                        "judge answers when the tensor judge fails)")
    for pod, (hist, feasible) in zip(pods, ref):
        e = got[pod.key]
        if (e.get("mode"), e.get("filters"), e.get("feasibleNow")) \
                != ("tensor", hist, feasible):
            problems.append(
                f"{pod.key}: tensor judge {e.get('filters')} with "
                f"{e.get('feasibleNow')} feasible ({e.get('mode')}), "
                f"oracle judge {hist} with {feasible}")
    return {"problems": problems, "pods": len(pods), "modes": modes,
            "verdicts": {k: {"filters": v.get("filters"),
                             "feasible": v.get("feasibleNow")}
                         for k, v in got.items()}}


def check_explain(nodes: list) -> dict:
    """A few pods no node can take."""
    from kubernetes_tpu.testing.wrappers import make_pod
    return _explain_vs_oracle(nodes, [], [
        make_pod("stuck-selector").req({"cpu": "100m"})
        .node_selector({"disk": "nvme"}).obj(),
        make_pod("stuck-cpu").req({"cpu": "1000"}).obj(),
        make_pod("stuck-both").req({"cpu": "1000"})
        .node_selector({"disk": "nvme"}).obj()])


def check_count_precision() -> dict:
    """Per-node match counts past bfloat16's exact integers. The domain
    aggregation (ops/topology.py _domain_counts) was a float32 matmul
    whose count operand a TPU's default precision rounded to bfloat16,
    exact up to 256 and not above — so: 301 matching pods on one node,
    300 on the other, hard zone spreads. With exact counts maxSkew 1
    refuses the fuller zone (302 - 300 > 1); 301 read as 300 admits it.
    It is a scatter/gather now; this case is what failed on the chip."""
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    zone = "topology.kubernetes.io/zone"
    nodes = [make_node(f"big{i}").capacity(
        {"cpu": "1000", "memory": "1000Gi", "pods": "1000"})
        .label(zone, f"z{i}").obj() for i in range(2)]
    bound = [make_pod(f"x{i}-{j}").label("app", "x").req({"cpu": "100m"})
             .node(f"big{i}").obj()
             for i, n in enumerate((301, 300)) for j in range(n)]
    out = _explain_vs_oracle(nodes, bound, [
        make_pod(f"probe-skew{skew}").label("app", "x").req({"cpu": "100m"})
        .spread(skew, zone, "DoNotSchedule", {"app": "x"}).obj()
        for skew in (1, 2)])
    out["counts"] = [301, 300]
    return out


def check_gang(nodes: list, pods: list) -> dict:
    """gang_schedule at the group path's shapes: validity by the
    sentinel's oracle judge (capacity audit + per-winner feasibility).
    serial=True is the repo's bit-for-bit ScheduleOne contract with the
    oracle (tests/test_gang.py): reported, because a float32 score that
    rounds differently on the chip moves an arg-max without making a
    placement invalid."""
    from kubernetes_tpu.audit.sentinel import (_unbound_view,
                                               verify_drain_winners)
    from kubernetes_tpu.encode.snapshot import SnapshotEncoder
    from kubernetes_tpu.models.gang import gang_schedule
    from kubernetes_tpu.sched.oracle import OracleScheduler
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, [], pending_pods=pods)
    pb = enc.encode_pods(pods, meta)
    a, rounds = gang_schedule(ct, pb, topo_keys=meta.topo_keys)
    winners = [(p, meta.node_names[int(x)])
               for p, x in zip(pods, a[:len(pods)]) if x >= 0]
    problems = verify_drain_winners(nodes, [], winners, [],
                                    max_checked=len(pods))
    if len(winners) != len(pods):
        problems.append(f"{len(winners)} of {len(pods)} pods placed on an "
                        "empty cluster")
    a_serial, _ = gang_schedule(ct, pb, topo_keys=meta.topo_keys,
                                serial=True)
    oracle = OracleScheduler(nodes, []).schedule_all(
        [_unbound_view(p) for p in pods])
    differ = [p.key for p, x, o in zip(pods, a_serial, oracle)
              if int(x) != (-1 if o is None else o)]
    return {"problems": problems, "pods": len(pods), "rounds": rounds,
            "placed": len(winners),
            "serial_vs_oracle": {"pods": len(pods),
                                 "differ": len(differ),
                                 "first": differ[:5]}}


def check_carve() -> dict:
    """carve_step: a 2x2x2 gang on a fragmented 4x4x4 torus, the
    member -> node picks bit-equal to the numpy oracle carver (the
    sentinel's carve judge)."""
    import numpy as np
    from kubernetes_tpu.audit.sentinel import verify_carve_assignments
    from kubernetes_tpu.encode.snapshot import (TENANT_KEY_ID,
                                                SnapshotEncoder)
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    from kubernetes_tpu.topology import carve
    from kubernetes_tpu.topology.slicing import (GANG_LABEL,
                                                 SLICE_SHAPE_LABEL,
                                                 is_contiguous_slice,
                                                 topology_labels)
    dims, shape = (4, 4, 4), (2, 2, 2)
    cells = [(x, y, z) for x in range(4) for y in range(4) for z in range(4)]
    nodes = []
    for x, y, z in cells:
        nb = make_node(f"tn-{x}-{y}-{z}").capacity(
            {"cpu": "8", "memory": "16Gi", "pods": "32"})
        for k, v in topology_labels(x, y, z).items():
            nb = nb.label(k, v)
        nodes.append(nb.obj())
    # near-full pods on spread-out cells: the carve must route around them
    bound = [make_pod(f"frag-{i}").req({"cpu": "7500m"})
             .node(f"tn-{x}-{y}-{z}").obj()
             for i, (x, y, z) in enumerate(cells[::9])]
    members = sorted(
        (make_pod(f"g-{m}").req({"cpu": "1"})
         .labels({GANG_LABEL: "g", SLICE_SHAPE_LABEL: "2x2x2"}).obj()
         for m in range(8)), key=lambda p: p.key)
    enc = SnapshotEncoder()
    ct, meta = enc.encode_cluster(nodes, bound, pending_pods=members)
    pb = enc.encode_pods(members, meta)
    labels = np.asarray(pb.pod_labels)
    tenant = (int(labels[0, TENANT_KEY_ID])
              if labels.shape[1] > TENANT_KEY_ID else -1)
    res = carve.carve_device(
        ct, np.asarray(pb.requests)[:len(members)].max(axis=0), tenant,
        np.zeros(ct.node_valid.shape[0], bool), dims, shape)
    asg = carve.select_assignment(res)
    if asg is None:
        return {"problems": ["the device carver found no 2x2x2 box on a "
                             "4x4x4 torus with 8 occupied cells"]}
    picks = {p.key: meta.node_names[ni] for p, ni in zip(members, asg)}
    problems = verify_carve_assignments(nodes, bound, {"g": picks}, members)
    where = {n.metadata.name: c for n, c in zip(nodes, cells)}
    if not is_contiguous_slice([where[n] for n in picks.values()], shape,
                               dims):
        problems.append(f"picked cells are not one contiguous box: {picks}")
    return {"problems": problems, "origins": int(res.fits.sum()),
            "picked": sorted(picks.values())}


def check_planner_overlay() -> dict:
    """The planners' resident programs: feasibility of a derived batch
    against the armed drain context, with and without appended node-group
    template rows, against OracleScheduler.feasible; the per-tenant quota
    plane against its definition."""
    import numpy as np
    from kubernetes_tpu.config.types import SchedulerConfiguration
    from kubernetes_tpu.encode.overlay import (ResidentPlanner,
                                               tenant_quota_mask)
    from kubernetes_tpu.sched.cache import SchedulerCache
    from kubernetes_tpu.sched.oracle import OracleScheduler
    from kubernetes_tpu.sched.queue import SchedulingQueue
    from kubernetes_tpu.sched.scheduler import Scheduler
    from kubernetes_tpu.testing.wrappers import make_node, make_pod
    nodes = [make_node(f"pn{i}").capacity(
        {"cpu": "16", "memory": "32Gi", "pods": "32"})
        .label("disk", "ssd" if i % 2 else "hdd").obj() for i in range(32)]
    bound = [make_pod(f"pb{i}").req({"cpu": "12"}).node(f"pn{i}").obj()
             for i in range(0, 32, 4)]
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    for p in bound:
        cache.add_pod(p)
    sched = Scheduler(SchedulerConfiguration(batch_size=8,
                                             max_drain_batches=2),
                      cache, SchedulingQueue(), lambda pod, node: True)
    problems: list[str] = []
    try:
        if not sched.warm_drain([make_pod(f"pw{i}").req({"cpu": "100m"})
                                 .obj() for i in range(8)],
                                slot_headroom=64):
            return {"problems": ["warm_drain did not arm the context"]}
        rp = ResidentPlanner(sched.resident_plan_view, cache)
        ctx = rp.plan_view(nodes, bound, "autoscaler")
        if ctx is None:
            return {"problems": [f"resident view declined: {rp.stats()}"]}
        pods = [make_pod("pl-small").req({"cpu": "2"}).obj(),
                make_pod("pl-mid").req({"cpu": "8"}).obj(),
                make_pod("pl-ssd").req({"cpu": "2"})
                .node_selector({"disk": "ssd"}).obj(),
                make_pod("pl-big").req({"cpu": "24"}).obj()]
        template = make_node("ng-big-t").capacity(
            {"cpu": "32", "memory": "64Gi", "pods": "32"}).obj()
        got = rp.mask_scores(ctx, pods, want_scores=True)
        over = rp.overlay_mask(ctx, [template], pods)
        if got is None or over is None:
            return {"problems": [f"overlay declined: {rp.stats()}"]}
        mask, scores, _reqs = got
        orc = OracleScheduler(nodes, bound)
        ref = np.asarray([orc.feasible(p)[0] for p in pods], bool)
        orc_t = OracleScheduler(nodes + [template], bound)
        ref_t = np.asarray([orc_t.feasible(p)[0] for p in pods], bool)
        if mask.shape != ref.shape or not np.array_equal(mask, ref):
            problems.append("plan mask differs from the oracle's")
        if not np.isfinite(scores[mask]).all():
            problems.append("non-finite score on a feasible node")
        if over[0].shape != ref_t.shape \
                or not np.array_equal(over[0], ref_t):
            problems.append("template-overlay mask differs from the "
                            "oracle's over nodes + template")
    finally:
        sched.close()
    tenants, quotas = [0, 0, 1, -1, 0, 1, 1], [2, 1]
    seen: dict = {}
    want = []
    for t in tenants:
        rank = seen.get(t, 0)
        seen[t] = rank + 1
        want.append(t < 0 or quotas[t] < 0 or rank < quotas[t])
    if tenant_quota_mask(tenants, quotas).tolist() != want:
        problems.append("tenant quota plane differs from its definition")
    return {"problems": problems, "pods": len(pods),
            "feasible": int(mask.sum()), "template_hosts": int(
                over[0][:, -1].sum())}


def _ran_on_device(name: str) -> bool:
    """The jitted function behind an EXERCISED entry holds a compiled
    program in this process (a check that never dispatched proves
    nothing)."""
    import importlib
    path, attr = name.split(":")
    mod = importlib.import_module(
        "kubernetes_tpu." + path[:-3].replace("/", "."))
    fn = getattr(mod, attr, None)
    return fn is not None and fn._cache_size() > 0


# ---- the chip-owning phases (children) ---------------------------------------

def _device_report(opts: dict) -> tuple[dict, list[str]]:
    """Name the device first; decide whether this run may go on."""
    import jax
    import jaxlib
    from benchmarks.connected import device_block
    try:
        import libtpu
        libtpu_v = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_v = None
    dev = device_block()
    say(opts, f"platform={dev['platform']} device_kind={dev['kind']!r} "
              f"devices={dev['count']} jax={jax.__version__} "
              f"jaxlib={jaxlib.__version__} libtpu={libtpu_v} "
              f"host_cores={os.cpu_count()} "
              f"compile_cache={jax.config.jax_compilation_cache_dir}")
    failures = []
    if opts["rehearsal"]:
        if dev["platform"] != "cpu":
            failures.append(f"--rehearsal is the CPU run; found platform "
                            f"{dev['platform']!r}")
    elif dev["platform"] != "tpu":
        failures.append(f"platform is {dev['platform']!r}, not 'tpu': no "
                        "accelerator, nothing to prove (--rehearsal runs "
                        "the gates on a CPU at a tiny size, and cannot "
                        "pass)")
    mesh = opts["mesh"]
    if mesh and dev["count"] < mesh[0] * mesh[1]:
        failures.append(f"--mesh {mesh[0]}x{mesh[1]} needs "
                        f"{mesh[0] * mesh[1]} devices, the backend has "
                        f"{dev['count']}: refusing the single-device "
                        "degrade")
    return dev, failures


def _size(opts: dict) -> dict:
    """Tiny only in a rehearsal, which can never print the pass line."""
    return TINY if opts["rehearsal"] else FULL


def _cfg_extra(opts: dict) -> dict:
    extra = {"parity_sample_every": 1}
    if opts["mesh"]:
        extra["mesh_shape"] = tuple(opts["mesh"])
    return extra


def served_gate(opts: dict, served: dict, error_logs: list,
                warned: list) -> list[str]:
    """The shared device gate, plus what only a clean smoke may demand:
    every drain judged, the asked-for mesh, no donation that became a
    copy, and nothing logged-and-carried-on — no ERROR record from the
    product and no loop error at ANY site (a clean run shows neither, on
    the CPU or the chip; some fallback sites keep no counter of their
    own, so the log is the only witness)."""
    from benchmarks.connected import (DEVICE_ERROR_SITES,
                                      check_served_on_device)
    gate = check_served_on_device(served)
    par = served.get("parity") or {}
    if par.get("every") != 1 or not (par.get("samples") or {}).get("drain"):
        gate.append(f"no drain was parity-judged: {par}")
    if opts["mesh"] and served.get("mesh_shape") != list(opts["mesh"]):
        gate.append(f"ran with meshShape {served.get('mesh_shape')}, not "
                    f"{list(opts['mesh'])}")
    donated = [w for w in warned if "donated" in w.lower()]
    if donated:
        gate.append(f"donation fell back to a copy: {donated[:3]}")
    gate += [f"loop error at {site}: {n:g}"
             for site, n in sorted((served.get("loop_errors") or {}).items())
             if n and site not in DEVICE_ERROR_SITES]
    gate += [f"logged and carried on: {line}" for line in error_logs]
    return gate


def served_phase(opts: dict) -> dict:
    """Chip owner #1: the served path at full size, then the small
    programs. -> {"device", "failures", "served", "programs", ...}."""
    import warnings
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    size = _size(opts)
    dev, failures = _device_report(opts)
    out: dict = {"device": dev, "failures": failures}
    if failures:
        return out
    failures += _declared_vs_found()

    from benchmarks.connected import drain_parity_check, run_connected
    from benchmarks.workloads import mixed_heterogeneous

    say(opts, f"served path: {size['pods']} pods x {size['nodes']} nodes, "
              f"batch {size['batch']} x {DRAIN_BATCHES}, seed "
              f"{opts['seed']}, mesh {opts['mesh'] or 'off'}, "
              "paritySampleEvery 1")
    t0 = time.time()
    # jax reports a donation that became a copy as a UserWarning
    with _error_logs() as errs, warnings.catch_warnings(record=True) as ws:
        warnings.simplefilter("always", UserWarning)
        served = run_connected(
            n_pods=size["pods"], n_nodes=size["nodes"],
            batch_size=size["batch"], drain_batches=DRAIN_BATCHES,
            timeout=opts["bind_timeout"], seed=opts["seed"],
            cfg_extra=_cfg_extra(opts), log=lambda *a: say(opts, *a))
    caught = sorted({f"{w.category.__name__}: {w.message}"[:300]
                     for w in ws if issubclass(w.category, UserWarning)})
    out["served"] = served
    out["served_s"] = round(time.time() - t0, 1)
    failures += [f"served: {g}"
                 for g in served_gate(opts, served, _log_lines(errs), caught)]
    say(opts, "served: " + _short({
        k: served.get(k) for k in (
            "bound", "pods", "nodes", "invariant_violations", "parity",
            "drains_dispatched", "resilience", "loop_errors",
            "schedule_attempts", "ctx_stats", "residency", "compile",
            "measure_s")}, 4000))
    out["served_error_logs"] = _log_lines(errs)[:20]
    out["warnings"] = caught[:20]
    if opts["mesh"]:
        out["mesh_parity"] = drain_parity_check(
            tuple(opts["mesh"]), n_nodes=size["nodes"], P=size["batch"],
            B=DRAIN_BATCHES, seed=opts["seed"])
        say(opts, f"mesh parity at the live shapes: {out['mesh_parity']}")
        if not out["mesh_parity"].get("ok"):
            failures.append("sharded drain differs from unsharded: "
                            f"{out['mesh_parity']}")

    # ---- the device programs the burst does not reach --------------------
    nodes, pods = mixed_heterogeneous(pods=64, nodes=size["nodes"],
                                      seed=opts["seed"])
    checks = (
        ("preemption", lambda: check_preemption(size["nodes"],
                                                size["preemptors"])),
        ("explain", lambda: check_explain(nodes)),
        ("gang", lambda: check_gang(nodes, pods[:32])),
        ("count_precision", check_count_precision),
        ("carve", check_carve),
        ("planner_overlay", check_planner_overlay),
    )
    out["programs"] = {}
    for name, fn in checks:
        t0 = time.time()
        try:
            with _error_logs() as errs:
                res = fn()
            res["problems"] = list(res.get("problems") or []) + [
                f"logged and carried on: {r}" for r in _log_lines(errs)]
        except Exception:
            res = {"problems": [traceback.format_exc()[-1500:]]}
        res["seconds"] = round(time.time() - t0, 1)
        out["programs"][name] = res
        failures += [f"{name}: {p}" for p in res["problems"]]
        say(opts, f"program {name}: "
                  f"{'ok' if not res['problems'] else 'FAILED'} "
                  + _short(res))
    cold = [k for k in EXERCISED if not _ran_on_device(k)]
    failures += [f"{k} is listed as exercised but compiled nothing in this "
                 "process" for k in cold]
    out["exercised"] = EXERCISED
    out["not_exercised"] = NOT_EXERCISED
    say(opts, "exercised: " + ", ".join(sorted(EXERCISED)))
    for k, why in sorted(NOT_EXERCISED.items()):
        say(opts, f"not_exercised: {k} — {why}")
    return out


def warm_phase(opts: dict) -> dict:
    """Chip owner #2, started after #1 has exited: the warm ladder alone,
    against an identical cluster. Everything must come from the compile
    cache."""
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    size = _size(opts)
    dev, failures = _device_report(opts)
    out: dict = {"device": dev, "failures": failures}
    if failures:
        return out
    from benchmarks.connected import run_warm_ladder
    warm = run_warm_ladder(
        n_pods=size["pods"], n_nodes=size["nodes"],
        batch_size=size["batch"], drain_batches=DRAIN_BATCHES,
        seed=opts["seed"], cfg_extra=_cfg_extra(opts),
        log=lambda *a: say(opts, *a))
    out["warm"] = warm
    comp = warm.get("compile") or {}
    say(opts, "warm ladder from the cache: " + _short(warm))
    if not comp.get("cacheHits"):
        failures.append(f"warm: no persistent-cache hit ({comp}); the "
                        "cache was not where the first process left it")
    if comp.get("realCompiles") != 0:
        failures.append(f"warm: {comp.get('realCompiles')} program(s) "
                        f"compiled again: {warm.get('missed')} (every "
                        "program persists — thresholds are 0 — so a miss "
                        "is a cache key that moved between processes)")
    if not (warm.get("residency") or {}).get("armed"):
        failures.append("warm: the resident context did not arm")
    return out


_PHASES = {"served": served_phase, "warm": warm_phase}


def _phase_child(name: str, opts: dict, conn) -> None:
    """spawn target: own process group (so the parent can stop everything
    the phase started), run, send the result."""
    os.setpgrp()
    sys.path.insert(0, HERE)
    try:
        result = _PHASES[name](opts)
    except BaseException:
        result = {"failures": [f"{name} phase died: "
                               + traceback.format_exc()[-3000:]]}
    try:
        conn.send(json.loads(json.dumps(result, default=str)))
    finally:
        conn.close()


# ---- the parent ----------------------------------------------------------------

def _run_phase(name: str, opts: dict, timeout: float) -> dict:
    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe(duplex=False)
    proc = ctx.Process(target=_phase_child, args=(name, opts, child))
    t0 = time.time()
    proc.start()
    child.close()
    result = None
    try:
        if parent.poll(timeout):
            result = parent.recv()
    except (EOFError, OSError):
        pass
    proc.join(timeout=30.0 if result is not None else 0.0)
    try:
        os.killpg(proc.pid, signal.SIGKILL)  # the phase and all it started
    except (ProcessLookupError, PermissionError):
        pass
    proc.join(timeout=10.0)
    if result is None:
        result = {"failures": [
            f"{name} phase gave no result within {timeout:.0f}s "
            f"(exit code {proc.exitcode})"]}
    result["phase_s"] = round(time.time() - t0, 1)
    return result


def _parse(argv) -> dict:
    def from_mesh(text: str) -> tuple:
        return tuple(int(x) for x in text.lower().split("x"))

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="makes the cluster and the pods (default 0)")
    ap.add_argument("--mesh", type=from_mesh, default=None, metavar="PxN",
                    help="run under meshShape [P, N] in one process that "
                         "owns P*N chips (default: none, one chip)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny sizes: runs every gate, can never "
                         "print the pass line or exit 0")
    args = ap.parse_args(argv)
    if args.mesh is not None and (len(args.mesh) != 2
                                  or min(args.mesh) < 1):
        ap.error("--mesh takes PxN, e.g. 1x4")
    return {"seed": args.seed, "mesh": args.mesh,
            "rehearsal": args.rehearsal, "bind_timeout": 300.0}


def main(argv=None) -> int:
    opts = _parse(argv)
    if not (os.path.isdir(os.path.join(HERE, "kubernetes_tpu"))
            and os.path.isdir(os.path.join(HERE, "benchmarks"))):
        say(opts, "chip_smoke.py drives the kubernetes_tpu program and it "
                  f"is not here ({HERE} holds no kubernetes_tpu/ and "
                  "benchmarks/): nothing ran")
        return EXIT_NO_PROGRAM
    report = {"argv": sys.argv[1:], "seed": opts["seed"],
              "mesh": opts["mesh"], "size": _size(opts),
              "reduced": REDUCED, "rehearsal": opts["rehearsal"]}
    failures: list[str] = []
    t0 = time.time()
    served = _run_phase("served", opts, SERVED_TIMEOUT_S)
    report["served_phase"] = served
    failures += served.get("failures") or []
    if not failures:
        # only after the first chip owner is gone: one process per chip
        warm = _run_phase("warm", opts, WARM_TIMEOUT_S)
        report["warm_phase"] = warm
        failures += warm.get("failures") or []
        cold = ((served.get("served") or {}).get("compile") or {})
        say(opts, "set-up seconds (for budgeting chip calls, not a "
                  f"performance record): first ladder "
                  f"{cold.get('warm_s')} with "
                  f"{(cold.get('warm') or {}).get('realCompiles')} "
                  "compiles, second ladder "
                  f"{(warm.get('warm') or {}).get('warm_s')} from the "
                  "cache; phases "
                  f"{served.get('phase_s')} + {warm.get('phase_s')}")
    report["failures"] = failures
    report["total_s"] = round(time.time() - t0, 1)
    say(opts, f"reduced: {json.dumps(REDUCED)}")
    try:
        os.makedirs(OUT_DIR, exist_ok=True)
        name = "rehearsal.json" if opts["rehearsal"] else (
            "report_mesh.json" if opts["mesh"] else "report.json")
        with open(os.path.join(OUT_DIR, name), "w") as f:
            json.dump(report, f, indent=1, default=str)
    except OSError as e:
        say(opts, f"could not write the report under {OUT_DIR}: {e}")
    return finish(opts, failures, served.get("device"))


def finish(opts: dict, failures: list, device) -> int:
    """The verdict. The pass line exists in exactly one branch: a TPU, no
    rehearsal, no failure."""
    if failures:
        for f in failures:
            say(opts, "FAIL: " + f)
        say(opts, f"chip_smoke: FAILED ({len(failures)} gate(s)) in "
                  f"{'rehearsal' if opts['rehearsal'] else 'chip'} mode")
        return EXIT_FAILED
    if opts["rehearsal"]:
        say(opts, "every gate held on the CPU at a tiny size; this says "
                  "nothing about the chip and is not a pass")
        return EXIT_REHEARSAL
    if not device or device.get("platform") != "tpu":
        say(opts, f"FAIL: no TPU behind this result: {device}")
        return EXIT_FAILED
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
