"""WatchStorm: >=10k watchers against a 3-node front door — follower
replicas absorb the fan-out, the leader barely notices.

The serving-plane claim this bench gates: list/watch load scales OUT
across read replicas instead of UP on the leader. A 3-node raft group
(one subprocess per node, ``chaos/replica.py``) serves the front door;
~10k storm watchers attach in two cohorts:

  phase A (baseline)  ~300 watchers on the LEADER only. Pod churn runs;
                      the leader's fan-out span (ns per event pushed
                      into watcher queues) is measured.
  phase B (storm)     the remaining ~10k watchers attach on the two
                      REPLICAS (replica-served share >= 2/3). The same
                      churn runs again; the leader's span is re-measured.

Storm watchers are in-process ``store.watch()`` queues inside each
replica subprocess (10k real HTTP streams would measure the bench
client, not the plane — the per-watcher queue put IS the fan-out cost);
sentinel informers ride REAL HTTP watch streams through the spread
client for end-to-end coverage.

Hard gates (missing number = failure, the PR-8 SLO discipline):
  - leader fan-out span growth phaseB/phaseA <= ``span_growth_max``
    (default 1.2x) with replica-served watcher share >= 2/3
  - gap-free streams: every watcher in a cohort reports the IDENTICAL
    event signature (count / rv-sum / rv-xor / last-rv) — one missed or
    reordered event anywhere splits the histogram
  - 0 slow-consumer drops, 0 severed streams across the whole storm
  - replica staleness bound honored: max sampled replay lag <= budget,
    and no replica /readyz flap while healthy
  - replica SIGKILL mid-churn heals: spread-client informer converges
    to the leader's exact pod set (zero loss), the reborn replica
    snapshot-resyncs to /readyz 200 within ``heal_slo_s``
  - 0 invariant violations (gap/loss/drop counts, summed)

Env knobs (bench.py): BENCH_WATCHSTORM=0 skips; BENCH_WATCHSTORM_WATCHERS
(default 10500), BENCH_WATCHSTORM_PODS (churn size per phase, default
600; clamped so a stalled cohort cannot overflow its queue budget),
BENCH_WATCHSTORM_SPAN_GROWTH (default 1.2), BENCH_WATCHSTORM_HEAL_SLO
(default 90s)."""

from __future__ import annotations

import threading
import time
import urllib.request


def _free_ports(n: int, host: str = "127.0.0.1") -> list:
    import socket
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind((host, 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


def _find_leader(procs, timeout: float = 60.0):
    """-> (leader proc, [follower procs]); raises if no single leader."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        roles = {p.node_id: p.call(("status",)) for p in procs}
        leaders = [p for p in procs
                   if roles[p.node_id].get("role") == "leader"]
        if len(leaders) == 1:
            return leaders[0], [p for p in procs if p is not leaders[0]]
        time.sleep(0.2)
    raise TimeoutError(f"no single leader: {roles}")


def _churn(client, prefix: str, n: int) -> int:
    """Create n pods (bulk chunks) then delete them all — 2n watch events
    through every live pod watcher. -> committed event count.

    The client's transport-retry contract: a retried NAMED write that
    already committed surfaces as 409 — so a 409 here means "done", not
    "broken". Names are unique per phase, so settling each item
    individually after a batch 409 cannot double-create (the store
    rejects duplicates before journaling)."""
    from kubernetes_tpu.client.clientset import ApiError
    from kubernetes_tpu.testing.wrappers import make_pod
    pods = client.pods("default")
    names = [f"{prefix}-{i}" for i in range(n)]
    for lo in range(0, n, 100):
        chunk = names[lo:lo + 100]
        try:
            pods.create_many([make_pod(nm).obj().to_dict()
                              for nm in chunk])
        except ApiError as e:
            if e.code != 409:
                raise
            for nm in chunk:  # the batch raced its own retry: settle
                try:
                    pods.create(make_pod(nm).obj().to_dict())
                except ApiError as e2:
                    if e2.code != 409:
                        raise
    for nm in names:
        try:
            pods.delete(nm)
        except ApiError as e:
            if e.code != 404:  # a retried delete that already landed
                raise
    return 2 * n


class _LagSampler:
    """Samples every replica's /frontdoor/status over HTTP while churn
    runs: max replay lag observed + readyz flaps on healthy replicas.
    HTTP (not the control pipe) so it can run beside the orchestrator."""

    def __init__(self, urls, period_s: float = 0.5):
        self.urls = list(urls)
        self.period_s = period_s
        self.max_lag_ms = 0.0
        self.samples = 0
        self.readyz_failures = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="watchstorm-lag-sampler")

    def _loop(self):
        import json as _json
        while not self._stop.is_set():
            for url in self.urls:
                try:
                    with urllib.request.urlopen(url + "/frontdoor/status",
                                                timeout=2.0) as resp:
                        st = _json.loads(resp.read())
                    lag = st.get("replayLagMs")
                    if lag is not None:
                        self.max_lag_ms = max(self.max_lag_ms, float(lag))
                        self.samples += 1
                    with urllib.request.urlopen(url + "/readyz",
                                                timeout=2.0):
                        pass
                except Exception:
                    self.readyz_failures += 1
            self._stop.wait(self.period_s)

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=10.0)


def run_watch_storm(n_watchers: int = 10500, churn_pods: int = 600,
                    leader_watchers: int = 300,
                    span_growth_max: float = 1.2,
                    min_replica_share: float = 2.0 / 3.0,
                    lag_budget_ms: float = 2000.0,
                    heal_slo_s: float = 90.0, log=print) -> dict:
    from kubernetes_tpu.chaos.replica import ReplicaProcess
    from kubernetes_tpu.client.clientset import HTTPClient
    from kubernetes_tpu.client.informer import SharedInformer
    from kubernetes_tpu.store.frontdoor import FrontDoorPublisher
    from kubernetes_tpu.store.store import WATCH_QUEUE_MAX

    # a stalled cohort-A queue holds BOTH phases' events (4*churn_pods);
    # overflowing the per-watcher budget by construction would gate on
    # the bench's own sizing, not the plane
    cap = WATCH_QUEUE_MAX // 4 - 64
    if churn_pods > cap:
        log(f"[watchstorm] churn {churn_pods} pods exceeds the per-watcher "
            f"queue budget for two phases; clamping to {cap}")
        churn_pods = cap
    # the baseline cohort must stay a sliver of the storm, whatever size
    # the env knobs pick — otherwise the replica-share gate measures the
    # bench's sizing, not the plane's routing
    leader_watchers = min(leader_watchers, max(1, n_watchers // 10))

    host = "127.0.0.1"
    raft_ports = _free_ports(3, host)
    api_ports = _free_ports(3, host)
    node_ids = [f"n{i}" for i in range(3)]
    raft_urls = {nid: f"http://{host}:{raft_ports[i]}"
                 for i, nid in enumerate(node_ids)}
    api_urls = {nid: f"http://{host}:{api_ports[i]}"
                for i, nid in enumerate(node_ids)}
    result: dict = {"case": "WatchStorm"}
    failures: list = []
    procs: list = []
    sampler = None
    informer = None
    try:
        for i, nid in enumerate(node_ids):
            peers = {p: raft_urls[p] for p in node_ids if p != nid}
            procs.append(ReplicaProcess(nid, raft_ports[i], api_ports[i],
                                        peers, api_urls,
                                        host=host).start())
        leader, replicas = _find_leader(procs)
        log(f"[watchstorm] leader={leader.node_id} "
            f"replicas={[r.node_id for r in replicas]}")
        for p in procs:
            p.wait_ready()
        endpoints = [p.url for p in procs]
        spread = HTTPClient(endpoints)
        leader_c = HTTPClient(leader.url)
        # the leader seeds system namespaces; followers skipped theirs
        for ns in ("default", "kube-system"):
            try:
                spread.resource("namespaces", None).create(
                    {"kind": "Namespace", "metadata": {"name": ns}})
            except Exception:
                pass  # AlreadyExists: the leader won the race

        def _quiesce_rv() -> int:
            _, rv = leader_c.pods("default").list_rv()
            for p in procs:
                if not p.call(("wait_rv", rv, 60.0)):
                    failures.append(f"{p.node_id} never replicated to "
                                    f"rv {rv} (stuck replica)")
            return rv

        def _leader_span() -> tuple:
            st = leader.call(("watch_stats",))
            return st["fanoutNs"], st["fanoutEvents"]

        # ---- phase A: leader-only fan-out baseline ----------------------
        rv0 = _quiesce_rv()
        a_leader = leader.call(("attach", "A", "Pod", leader_watchers, rv0))
        a_refs = sum(r.call(("attach", "A", "Pod", 1, rv0))["attached"]
                     for r in replicas)
        attached_a = a_leader["attached"] + a_refs
        sampler = _LagSampler([r.url for r in replicas]).start()
        ns0, ev0 = _leader_span()
        t0 = time.monotonic()
        _churn(spread, "storm-a", churn_pods)
        rv1 = _quiesce_rv()
        ns1, ev1 = _leader_span()
        span_a = (ns1 - ns0) / max(1, ev1 - ev0)
        result["phaseA"] = {
            "watchers": attached_a, "churn_s": round(
                time.monotonic() - t0, 2),
            "leaderSpanNsPerEvent": round(span_a, 1)}
        log(f"[watchstorm] phase A: {attached_a} leader-side watchers, "
            f"span {span_a:.0f} ns/event")

        # ---- phase B: the storm lands on the replicas -------------------
        per_replica = max(1, -(-(n_watchers - attached_a - 1)
                               // len(replicas)))
        b_replica = sum(r.call(("attach", "B", "Pod", per_replica, rv1),
                               timeout=300.0)["attached"]
                        for r in replicas)
        b_leader = leader.call(("attach", "B", "Pod", 1, rv1))["attached"]
        total = attached_a + b_replica + b_leader
        replica_share = (a_refs + b_replica) / total
        t0 = time.monotonic()
        _churn(spread, "storm-b", churn_pods)
        rv2 = _quiesce_rv()
        ns2, ev2 = _leader_span()
        span_b = (ns2 - ns1) / max(1, ev2 - ev1)
        result["phaseB"] = {
            "watchers": total, "replicaShare": round(replica_share, 3),
            "churn_s": round(time.monotonic() - t0, 2),
            "leaderSpanNsPerEvent": round(span_b, 1)}
        growth = span_b / max(span_a, 1.0)
        result["leaderSpanGrowth"] = round(growth, 3)
        log(f"[watchstorm] phase B: {total} watchers "
            f"({replica_share:.0%} replica-served), span {span_b:.0f} "
            f"ns/event, growth {growth:.2f}x")

        # ---- gap-free verification (before anything dies) ---------------
        gap_violations = severed = 0
        for cohort, expect in (("A", attached_a),
                               ("B", b_replica + b_leader)):
            sigs: dict = {}
            for p in procs:
                got = p.call(("collect", cohort), timeout=300.0)
                severed += got["severed"]
                for k, v in got["signatures"].items():
                    sigs[k] = sigs.get(k, 0) + v
            distinct, counted = len(sigs), sum(sigs.values())
            result[f"cohort{cohort}"] = {
                "watchers": counted, "distinctSignatures": distinct}
            if distinct != 1:
                gap_violations += distinct - 1
                failures.append(
                    f"cohort {cohort}: {distinct} distinct event "
                    f"signatures across {counted} watchers (gap or "
                    f"reorder somewhere): {list(sigs.items())[:4]}")
            if counted != expect:
                failures.append(f"cohort {cohort}: {counted} watchers "
                                f"reported, {expect} attached")
        drops = sum(p.call(("watch_stats",))["dropsTotal"] for p in procs)
        result["drops"] = drops
        result["severedStreams"] = severed
        # the staleness window closes BEFORE the disaster leg: the bound
        # is a promise about healthy replicas, and a SIGKILLed one is
        # supposed to go unready
        sampler.stop()
        result["staleness"] = {
            "maxReplayLagMs": round(sampler.max_lag_ms, 1),
            "samples": sampler.samples,
            "budgetMs": lag_budget_ms,
            "readyzFailures": sampler.readyz_failures}

        # ---- disaster leg: SIGKILL one replica mid-churn ----------------
        informer = SharedInformer(spread.pods("default")).start()
        if not informer.wait_for_cache_sync(30.0):
            failures.append("sentinel informer never synced")
        victim = replicas[0]
        heal_pods = [f"heal-{i}" for i in range(100)]
        from kubernetes_tpu.testing.wrappers import make_pod
        killed_at = None
        from kubernetes_tpu.client.clientset import ApiError
        for i, nm in enumerate(heal_pods):
            if i == len(heal_pods) // 3:
                victim.kill()
                killed_at = nm
            try:
                spread.pods("default").create(
                    make_pod(nm).obj().to_dict())
            except ApiError as e:
                if e.code != 409:  # retried-but-committed is a success
                    raise
        log(f"[watchstorm] killed {victim.node_id} at {killed_at}; "
            "churn continued through the outage")
        heal_s = victim.restart(ready_timeout=heal_slo_s)
        result["heal"] = {"replica": victim.node_id,
                          "readyz_s": round(heal_s, 2)}
        # readyz 200 means "caught up to the commit frontier I last saw";
        # pin the divergence check to the leader's CURRENT rv
        _, heal_rv = leader_c.pods("default").list_rv()
        if not victim.call(("wait_rv", heal_rv, 30.0)):
            failures.append(f"reborn {victim.node_id} never replicated "
                            f"to rv {heal_rv}")
        # zero loss: the spread-client informer converges to the exact
        # leader pod set despite its endpoint dying under it
        leader_names = {p["metadata"]["name"]
                        for p in leader_c.pods("default").list()}
        deadline = time.monotonic() + 60.0
        informer_names: set = set()
        while time.monotonic() < deadline:
            informer_names = {p["metadata"]["name"]
                              for p in informer.store.list()}
            if informer_names == leader_names:
                break
            time.sleep(0.25)
        missing = leader_names - informer_names
        phantom = informer_names - leader_names
        result["heal"]["informerMissing"] = len(missing)
        result["heal"]["informerPhantom"] = len(phantom)
        if missing or phantom:
            failures.append(
                f"informer lost events through the replica kill: "
                f"{len(missing)} missing (first {sorted(missing)[:3]}), "
                f"{len(phantom)} phantom")
        # the reborn replica snapshot-resynced to the same state
        reborn_names = {p["metadata"]["name"] for p in
                        HTTPClient(victim.url).pods("default").list()}
        if reborn_names != leader_names:
            failures.append(
                f"reborn {victim.node_id} diverges from the leader: "
                f"{len(leader_names ^ reborn_names)} differing pods")
        # publish the front-door ConfigMap once — ktpu status coverage
        FrontDoorPublisher(spread, endpoints).publish_once()

        # ---- gates (missing number = failure) ---------------------------
        if span_a <= 0 or span_b <= 0:
            failures.append("leader fan-out span missing — no events "
                            "were fanned during a measured phase")
        elif growth > span_growth_max:
            failures.append(f"leader fan-out span grew {growth:.2f}x "
                            f"under the storm (gate {span_growth_max}x)")
        if replica_share < min_replica_share:
            failures.append(f"replica-served share {replica_share:.2f} "
                            f"below {min_replica_share:.2f} — the storm "
                            "didn't land on the replicas")
        if total < min(n_watchers, 1000):
            failures.append(f"only {total} watchers attached "
                            f"(asked {n_watchers})")
        if drops:
            failures.append(f"{drops} slow-consumer drops during a storm "
                            "sized to fit every queue budget")
        if severed:
            failures.append(f"{severed} storm streams severed mid-storm")
        if sampler.samples == 0:
            failures.append("no replica lag samples collected — the "
                            "staleness bound went unmeasured")
        elif sampler.max_lag_ms > lag_budget_ms:
            failures.append(f"replica replay lag peaked at "
                            f"{sampler.max_lag_ms:.0f}ms "
                            f"(budget {lag_budget_ms:.0f}ms)")
        if sampler.readyz_failures:
            failures.append(f"{sampler.readyz_failures} /readyz probes "
                            "failed on replicas that were supposed to be "
                            "healthy (flap during the storm)")
        result["invariant_violations"] = (gap_violations + severed
                                          + drops + len(missing)
                                          + len(phantom))
    except Exception as e:  # a dead bench must fail loudly, not silently
        failures.append(f"bench crashed: {type(e).__name__}: {e}")
        result.setdefault("invariant_violations", None)
    finally:
        if sampler is not None and sampler._thread.is_alive():
            sampler.stop()
        if informer is not None:
            informer.stop()
        for p in procs:
            try:
                p.stop()
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
    _log = lambda *a: print(*a, file=sys.stderr)  # noqa: E731
    res = run_watch_storm(
        n_watchers=int(os.environ.get("BENCH_WATCHSTORM_WATCHERS",
                                      "10500")),
        churn_pods=int(os.environ.get("BENCH_WATCHSTORM_PODS", "600")),
        span_growth_max=float(os.environ.get(
            "BENCH_WATCHSTORM_SPAN_GROWTH", "1.2")),
        heal_slo_s=float(os.environ.get("BENCH_WATCHSTORM_HEAL_SLO",
                                        "90")),
        log=_log)
    print(json.dumps(res))
    if res.get("slo_failures") or res.get("invariant_violations"):
        sys.exit(1)
