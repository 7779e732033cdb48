"""The benchmark's own processes: the apiserver, the watcher that stamps
binds and deletes, and the sender that creates pods. Each is a spawned
interpreter of its own, so none shares the scheduler's GIL, and none
imports jax. All stamps are ``time.monotonic()``, which on Linux is one
clock for every process of the machine."""

from __future__ import annotations

import threading
import time

from .reference import key


def serve(conn) -> None:
    """In-memory store and HTTP apiserver until any message arrives."""
    from kubernetes_tpu.store.apiserver import APIServer
    server = APIServer().start()
    conn.send(server.port)
    conn.recv()
    server.stop()


def watch(url: str, rv0: int, count, stop, conn) -> None:
    """Stamp each pod's first watch event that carries ``spec.nodeName``,
    in every namespace, and each pod's ``DELETED`` event with the object
    as the event carried it. ``count`` follows the number of binds
    stamped. On ``stop`` sends {"binds": {"ns/name": [t, node]}, "gone":
    {"ns/name": [t, last object]}, "restarts": n}; a stream that closes is
    opened again from the last resourceVersion seen."""
    from kubernetes_tpu.client.clientset import HTTPClient
    pods = HTTPClient(url, timeout=30.0).resource("pods", None)
    binds: dict = {}
    gone: dict = {}
    restarts, rv = 0, rv0
    w = pods.watch(since_rv=rv)
    conn.send("ready")
    while not stop.is_set():
        if w.closed:
            restarts += 1
            w = pods.watch(since_rv=rv)
        ev = w.get()
        if ev is None:
            continue
        rv = max(rv, ev.resource_version)
        obj = ev.object or {}
        if ev.type == "DELETED":
            gone[key(obj)] = [time.monotonic(), obj]
            continue
        node = obj.get("spec", {}).get("nodeName")
        if node and key(obj) not in binds:
            binds[key(obj)] = [time.monotonic(), node]
            count.value = len(binds)
    w.stop()
    conn.send({"binds": binds, "gone": gone, "restarts": restarts})


def send(url: str, groups: list, threads: int, go, conn) -> None:
    """Create pods on a schedule. ``groups`` is [(due_s, namespace,
    [pod, ...])] in due order; after ``go`` each group is sent with one
    bulk create at origin + due_s, by the first of ``threads`` senders that
    is free. Sends ("t0", origin) when the clock starts, then
    ("done", [[sent_s, returned_s, error or None], ...]) in group order,
    both relative to the origin."""
    from kubernetes_tpu.client.clientset import HTTPClient
    client = HTTPClient(url, timeout=120.0)
    out: list = [None] * len(groups)
    lock = threading.Lock()
    nxt = [0]
    go.wait()
    t0 = time.monotonic()
    conn.send(("t0", t0))

    def work() -> None:
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= len(groups):
                return
            due, ns, objs = groups[i]
            wait = t0 + due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            sent = time.monotonic()
            err = None
            try:
                client.pods(ns).create_many(objs)
            except Exception as e:  # reported per group; the run goes on
                err = f"{type(e).__name__}: {e}"[:300]
            out[i] = [sent - t0, time.monotonic() - t0, err]

    pool = [threading.Thread(target=work, daemon=True)
            for _ in range(max(1, threads))]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    conn.send(("done", out))
