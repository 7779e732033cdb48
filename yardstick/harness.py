"""One run of one cell: resolve its files by name, set the deployment up,
measure one window from the client's side, judge the answers after it.

Nothing here names a cell, a configuration, a traffic mix or a per-layer
metric: a cell is an entry of BENCHMARK.json (or ``<config>.<traffic>`` for
files no entry lists), a configuration names its generator, a mix names its
kind, a layer metric names its reader. See README.md.
"""

from __future__ import annotations

import importlib
import json
import multiprocessing as mp
import os
import shutil
import sys
import time
import traceback

from . import procs, program, verdicts, xplane
from .reference import key

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")

EXIT_NO_DEVICE = 3
EXIT_SETUP = 4


def say(msg: str) -> None:
    print(msg, flush=True)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str) -> dict:
    """-> {"name", "config", "traffic", "chips", "listed"}: the entry of
    BENCHMARK.json with that name, else ``<config>.<traffic>`` split at the
    last dot (files that no entry lists: a rehearsal, a sweep)."""
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    bench = _json(bench_path) if os.path.exists(bench_path) else {}
    for w in bench.get("workloads", []):
        if w["name"] == workload:
            return {"name": workload, "config": w["config"],
                    "traffic": w["traffic"], "chips": int(w["chips"]),
                    "listed": bench}
    config, _, traffic = workload.rpartition(".")
    if not config:
        raise SystemExit(f"workload {workload!r} is neither in "
                         "BENCHMARK.json nor <config>.<traffic>")
    return {"name": workload, "config": config, "traffic": traffic,
            "chips": 1, "listed": None}


def listed_metrics(cell: dict, group: str):
    """Names of the ``group`` metrics BENCHMARK.json gives this cell, or
    None for a cell it does not list (which then reports all it has)."""
    if cell["listed"] is None:
        return None
    return {m["name"] for m in cell["listed"].get(group, [])
            if "workloads" not in m or cell["name"] in m["workloads"]}


def layer_metrics(kind: str) -> list:
    """The layer-metric files that this traffic kind reports."""
    folder = os.path.join(HERE, "layer_metrics")
    out = []
    for name in sorted(os.listdir(folder)):
        if name.endswith(".json"):
            spec = _json(os.path.join(folder, name))
            if kind in spec["kinds"]:
                out.append(spec)
    return out


PHASES = ("measure", "init", "warmup", "pending")


def build(config: dict, driver, params: dict, seed: int,
          seconds: float) -> dict:
    """Everything the run will create, known before it starts: nodes, the
    pods of each phase with their namespaces, and the window's groups. A
    generator with ``generate_phases`` is told every phase's count; one
    with ``generate`` alone gets the total and is sliced measure | init |
    warmup."""
    gen = importlib.import_module(
        f"yardstick.generators.{config['generator']}")
    plan = driver.plan(params, config, seed, seconds)
    counts = {"measure": sum(n for _, n in plan["groups"]),
              "init": int(config["initPods"]),
              "warmup": int(config["warmupPods"]),
              "pending": int(config.get("pendingPods", 0))}
    leavers = tuple(config.get("leavers", ()))
    if not set(leavers) <= {"init", "warmup"}:
        raise SystemExit(f"leavers {list(leavers)}: only pods bound in "
                         "set-up (init, warmup) may leave; a measured pod "
                         "that is gone is failed")
    if hasattr(gen, "generate_phases"):
        nodes, phases = gen.generate_phases(seed, int(config["nodes"]),
                                            dict(counts))
        got = {phase: len(group) for phase, group in phases.items()}
        if got != counts:
            raise SystemExit(f"generator {config['generator']} gave {got} "
                             f"pods where {counts} were asked for")
    elif counts["pending"]:
        raise SystemExit(
            f"pendingPods {counts['pending']} needs a generator with "
            f"generate_phases(seed, nodes, counts); {config['generator']} "
            "has generate(seed, nodes, pods) only, which is sliced measure "
            "| init | warmup")
    else:
        n_measure, n_init = counts["measure"], counts["init"]
        nodes, pods = gen.generate(seed, int(config["nodes"]),
                                   n_measure + n_init + counts["warmup"])
        phases = {"measure": pods[:n_measure],
                  "init": pods[n_measure:n_measure + n_init],
                  "warmup": pods[n_measure + n_init:], "pending": []}
    for phase, group in phases.items():
        for p in group:
            p["metadata"]["namespace"] = config["namespaces"][phase]
    return {"nodes": nodes, "phases": phases, "plan": plan,
            "leavers": leavers, "constraints": tuple(gen.CONSTRAINTS)}


class Deployment:
    """The cell's deployment for one run: an apiserver process holding the
    nodes, a watcher process, a sender process loaded with the window's
    groups, and the scheduler in this process. ``with`` stops and joins all
    of it."""

    def __init__(self, config: dict, world: dict):
        self.config, self.world = config, world
        self.children, self.runner = [], None
        measure = world["phases"]["measure"]
        # bound during set-up, before the window
        self.before = world["phases"]["init"] + world["phases"]["warmup"]
        # created in set-up and not awaited: the configuration states that
        # they fit nowhere
        self.pending = world["phases"]["pending"]
        self.groups, at = [], 0
        for due, n in world["plan"]["groups"]:
            self.groups.append((due, config["namespaces"]["measure"],
                                measure[at:at + n]))
            at += n
        ctx = mp.get_context("spawn")  # never fork a live TPU client
        self.server_conn = self._spawn(ctx, procs.serve)
        try:
            url = f"http://127.0.0.1:{self.server_conn.recv()}"
            self.client = program.client(url)
            spaces = self.client.resource("namespaces", None)
            have = {n["metadata"]["name"] for n in spaces.list()}
            for ns in sorted(set(config["namespaces"].values()) - have):
                spaces.create({"apiVersion": "v1", "kind": "Namespace",
                               "metadata": {"name": ns}})
            self.client.nodes().create_many(world["nodes"])
            _, rv0 = self.client.resource("pods", None).list_rv()
            self.count, self.stop_watch = ctx.Value("i", 0), ctx.Event()
            self.watch_conn = self._spawn(ctx, procs.watch, url, rv0,
                                          self.count, self.stop_watch)
            self.watcher = self.children[-1]
            self.go = ctx.Event()
            self.send_conn = self._spawn(
                ctx, procs.send, url, self.groups,
                world["plan"]["threads"], self.go)
            # the existing-pod bucket is sized here for every pod the cell
            # will create, so no run outgrows it inside the window
            self.runner = program.start_scheduler(
                url, config["scheduler"],
                measure + self.before + self.pending,
                headroom=len(measure) + len(self.before)
                + len(self.pending))
            if not self.watch_conn.poll(60.0) or (
                    self.watch_conn.recv() != "ready"):
                raise RuntimeError("the watcher did not come up")
        except BaseException:
            self.close()
            raise

    def _spawn(self, ctx, target, *args):
        """Start ``target(*args, conn)`` in a process of its own; -> this
        end of its pipe."""
        mine, theirs = ctx.Pipe()
        proc = ctx.Process(target=target, args=(*args, theirs), daemon=True)
        proc.start()
        self.children.append(proc)
        return mine

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self) -> None:
        if self.runner is not None:
            self.runner.stop()
        try:
            self.server_conn.send("stop")
        except OSError:
            pass
        for proc in self.children:
            proc.join(timeout=10.0)
            if proc.is_alive():
                proc.kill()
                proc.join()

    def bound_count(self) -> int:
        if not self.watcher.is_alive():
            raise RuntimeError("the watcher died")
        return self.count.value

    def warm_up(self) -> None:
        """One regime for every run: a warm-up drain through the served
        path, loop running — the configuration's initial pods, its warm-up
        pods — created, bound and seen bound before the window. So a
        cache-booted process's canary sample, lazy initialisation and the
        first staging swap are set-up in cold and warm runs alike. Then the
        pending phase (upstream's ``skipWaitToCompletion``): created, and
        waited for only until the scheduler has called as many attempts
        unschedulable as there are such pods, so that every window opens
        on the pool parked after one attempt."""
        self._create(self.before)
        if not program.wait_until(
                lambda: self.bound_count() >= len(self.before), 240.0):
            raise RuntimeError(f"warm-up: {self.count.value} of "
                               f"{len(self.before)} pods seen bound")
        if self.pending:
            def judged() -> float:
                return program.counters(self.runner).get(
                    program.UNSCHEDULABLE, 0.0)

            floor = judged() + len(self.pending)
            self._create(self.pending)
            if not program.wait_until(lambda: judged() >= floor, 240.0,
                                      every=0.1):
                raise RuntimeError(
                    f"warm-up: {judged() - floor + len(self.pending):g} "
                    f"unschedulable attempts for {len(self.pending)} "
                    "pending pods")

    def _create(self, pods: list) -> None:
        """One bulk create a namespace, in the pods' order."""
        by_ns: dict = {}
        for p in pods:
            by_ns.setdefault(p["metadata"]["namespace"], []).append(p)
        for ns, objs in by_ns.items():
            self.client.pods(ns).create_many(objs)

    def window(self, seconds: float, trace_dir) -> dict:
        """Open the window, let the sender go, wait until every pod is
        seen bound or the plan's deadline, close the window; then collect
        what the watcher and the sender hold. ``trace_dir``: wrap the
        window in a profiler trace there. The pending phase is not
        expected: it never binds."""
        plan, runner = self.world["plan"], self.runner
        if trace_dir:
            import jax
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        program.open_window()
        c_open = program.counters(runner)
        if trace_dir:
            with jax.profiler.TraceAnnotation(xplane.MARK_START,
                                              t=repr(time.time())):
                pass
        self.go.set()
        _tag, t0 = self.send_conn.recv()
        expected = len(self.before) + sum(n for _, n in plan["groups"])
        pending_at = {0.5: None, 1.0: None}  # share of --seconds -> pods
        while (time.monotonic() < t0 + plan["deadline_s"]
               and self.bound_count() < expected):
            for share, value in pending_at.items():
                if value is None and time.monotonic() >= (
                        t0 + share * seconds):
                    pending_at[share] = program.pending(runner)
            time.sleep(0.02)
        t_end = time.monotonic()
        if trace_dir:
            with jax.profiler.TraceAnnotation(xplane.MARK_END,
                                              t=repr(time.time())):
                pass
        c_close = program.counters(runner)
        spans = program.window_spans()
        if trace_dir:
            jax.profiler.stop_trace()
        # the window is closed: nothing below is timed
        self.stop_watch.set()
        if not self.watch_conn.poll(30.0):
            raise RuntimeError("the watcher did not answer")
        seen = self.watch_conn.recv()
        if not self.send_conn.poll(150.0):
            raise RuntimeError("the sender did not finish")
        _tag, sent = self.send_conn.recv()
        return {"t0": t0, "t_end": t_end, "spans": spans,
                "counters": program.delta(c_open, c_close),
                "pending_at": pending_at, "binds": seen["binds"],
                "gone": seen["gone"], "watch_restarts": seen["restarts"],
                "sent": sent}

    def judge(self, platform: str, binds: dict, gone: dict) -> tuple:
        """After the window, outside every timed interval. -> (verdicts,
        keys of binds the store does not confirm). ``gone``: the pods the
        watcher saw deleted, {key: [t, last object]}."""
        pods = self.client.resource("pods", None).list()
        may_leave = {key(p) for phase in self.world["leavers"]
                     for p in self.world["phases"][phase]}
        back, unconfirmed = verdicts.read_back(binds, pods, gone, may_leave)
        results = [back]
        if self.pending:
            results.append(verdicts.left_pending(
                [key(p) for p in self.pending], pods, binds))
        results += verdicts.end_state(
            self.world["constraints"], self.client.nodes().list(), pods,
            [obj for _t, obj in gone.values()])
        results += verdicts.own_judges(program.settle(self.runner))
        results.append(verdicts.device_answers(
            platform, program.residency(self.runner),
            program.resilience(self.runner),
            program.counters(self.runner)))
        return results, unconfirmed


def per_pod(groups: list, win: dict, deadline: float,
            unconfirmed: set) -> tuple:
    """Per measured pod, in seconds after the window's origin: when it was
    due, and when it was seen bound — None unless that was inside the
    deadline and the store confirms the bind."""
    due, bound = [], []
    for g_due, _ns, objs in groups:
        for p in objs:
            hit = win["binds"].get(key(p))
            t = None if hit is None else hit[0] - win["t0"]
            due.append(g_due)
            bound.append(t if t is not None and t <= deadline
                         and key(p) not in unconfirmed else None)
    return due, bound


def per_layer(cell: dict, kind: str, facts: dict) -> dict:
    """Every layer metric of this traffic kind whose reader finds
    something to read."""
    wanted = listed_metrics(cell, "per_layer")
    out = {}
    for spec in layer_metrics(kind):
        if wanted is not None and spec["name"] not in wanted:
            continue
        reader = importlib.import_module(
            f"yardstick.readers.{spec['reader']}")
        value = reader.read(facts, spec["args"])
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def pace_and_regime(win: dict, spans: dict, setup_s: float,
                    pending: list) -> dict:
    """What is reported beside the result and never judged. ``pending``:
    keys of the pods created in set-up and not awaited."""
    window = win["counters"]
    create_errors = [s[2] for s in win["sent"] if s[2]]
    return {
        "window_s": win["t_end"] - win["t0"], "setup_s": setup_s,
        "pending_mid_window": win["pending_at"][0.5],
        "pending_at_seconds": win["pending_at"][1.0],
        "drains": window.get("scheduler_pipeline_depth_count", 0.0),
        "ctx_rebuilds": window.get("ctx.rebuilds", 0.0),
        "ctx_reasons": {k[len("ctx.reason."):]: v for k, v in window.items()
                        if k.startswith("ctx.reason.") and v},
        "window_compiles": window.get("compile.real", 0.0),
        "bind_retries": window.get("scheduler_bind_retries_total", 0.0),
        "loop_errors": {k: v for k, v in window.items()
                        if k.startswith("scheduler_loop_errors_total") and v},
        "watch_restarts": win["watch_restarts"],
        "pending_pods": len(pending),
        "pending_bound": sum(k in win["binds"] for k in pending),
        "left": len(win["gone"]),
        "create_errors": len(create_errors),
        "first_create_errors": create_errors[:3],
        "span_ms": {k: round(v["ms"], 1) for k, v in spans.items()},
    }


def run(args, t_start: float) -> int:
    cell = resolve(args.workload)
    config = _json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    params = _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    driver = importlib.import_module(f"yardstick.drivers.{params['kind']}")
    rehearsal = bool(config.get("rehearsal"))
    if rehearsal and cell["listed"] is not None:
        raise SystemExit("a rehearsal configuration may not be listed in "
                         "BENCHMARK.json")
    device = program.boot()
    if not rehearsal and (device["platform"] != "tpu"
                          or device["count"] < cell["chips"]):
        print(f"yardstick: {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"jax reports {device}. Nothing measured.", file=sys.stderr)
        return EXIT_NO_DEVICE
    say(f"yardstick: {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} on {device}")

    world = build(config, driver, params, args.seed, args.seconds)
    trace_dir = (os.path.join(OUT, "trace", f"{cell['name']}.{args.seed}")
                 if args.trace else None)
    try:
        with Deployment(config, world) as dep:
            dep.warm_up()
            win = dep.window(args.seconds, trace_dir)
            results, unconfirmed = dep.judge(device["platform"],
                                             win["binds"], win["gone"])
            device["memory_peak_bytes"] = program.memory_peak_bytes()
            groups = dep.groups
    except Exception:
        traceback.print_exc()
        print("yardstick: set-up or collection failed; no result",
              file=sys.stderr)
        return EXIT_SETUP

    due, bound = per_pod(groups, win, world["plan"]["deadline_s"],
                         unconfirmed)
    setup_s = win["t0"] - t_start
    spans: dict = {}
    for name, s, e in win["spans"]:
        tot = spans.setdefault(name, {"ms": 0.0, "n": 0})
        tot["ms"] += (e - s) * 1000.0
        tot["n"] += 1
    n_bound = sum(t is not None for t in bound)
    breakdown = None
    if args.trace:
        facts = {"kind": params["kind"], "seconds": args.seconds,
                 "attempted": len(due), "bound": n_bound,
                 "groups": [[g[0], len(g[2]), *s]
                            for g, s in zip(groups, win["sent"])],
                 "spans": spans, "counters": win["counters"],
                 "trace": None}
        try:
            facts["trace"] = trace = xplane.reduce(
                xplane.newest_trace(trace_dir), win["spans"])
        except xplane.NoDevicePlane:
            if not rehearsal:  # a CPU rehearsal has no device to trace
                raise
        else:
            device.update(busy_s=trace["busy_s"],
                          window_s=trace["window_s"])
            breakdown = {"device_ops": trace["device_ops"],
                         "idle_gaps": trace["idle_gaps"]}
        metrics = per_layer(cell, params["kind"], facts)
    else:
        found = driver.metrics({"seconds": args.seconds,
                                "deadline_s": world["plan"]["deadline_s"],
                                "due": due, "bound": bound})
        found["setup_s"] = setup_s
        units = dict(driver.E2E, setup_s="s")
        wanted = listed_metrics(cell, "end_to_end")
        metrics = {name: {"value": value, "unit": units[name]}
                   for name, value in found.items()
                   if wanted is None or name in wanted}

    beside = pace_and_regime(win, spans, setup_s,
                             [key(p) for p in world["phases"]["pending"]])
    say("counters: " + json.dumps(beside))
    for name, ok, detail, _n in results:
        if not ok:
            say(f"VERDICT FAILED {name}: {detail}")
    line = {"correct": all(ok for _, ok, _, _ in results),
            "attempted": len(due), "failed": len(due) - n_bound,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # every number ``correct`` compared, beside its limit: last in the
    # line, and the last lines of standard error
    line["compared"] = {name: {"value": n, "limit": 0}
                        for name, _, _, n in results}
    full = dict(line, workload=cell["name"], seed=args.seed,
                seconds=args.seconds, trace=args.trace, counters=beside,
                verdicts=[{"name": n, "ok": ok, "detail": d}
                          for n, ok, d, _ in results])
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{cell['name']}.{args.seed}.json"),
              "w") as f:
        json.dump(full, f, indent=1)
    for name, number in line["compared"].items():
        print(f"compared {name}: {number['value']} limit "
              f"{number['limit']}", file=sys.stderr)
    sys.stderr.flush()
    say(json.dumps(line))
    return 0
