"""Plain Kubernetes JSON objects, as a client would POST them: no uid, no
resourceVersion, no creationTimestamp (the apiserver stamps those). Shared
by the generators of this directory; imports nothing from the program."""

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
ZONES = [f"zone-{i}" for i in range(10)]


def node(name: str, capacity: dict, labels: dict | None = None,
         taints: list | None = None) -> dict:
    """core/v1 Node; the hostname label comes first, as a kubelet sets it."""
    return {
        "apiVersion": "v1", "kind": "Node",
        "metadata": {"name": name, "namespace": "",
                     "labels": {HOSTNAME: name, **(labels or {})}},
        "spec": {"taints": taints} if taints else {},
        "status": {"allocatable": dict(capacity),
                   "capacity": dict(capacity)},
    }


def pod(name: str, requests: dict, labels: dict | None = None,
        namespace: str = "default", **spec) -> dict:
    """core/v1 Pod with one container; ``spec`` adds nodeSelector,
    tolerations, affinity, topologySpreadConstraints."""
    meta: dict = {"name": name, "namespace": namespace}
    if labels:
        meta["labels"] = dict(labels)
    return {
        "apiVersion": "v1", "kind": "Pod", "metadata": meta,
        "spec": {"schedulerName": "default-scheduler",
                 "restartPolicy": "Always", **spec,
                 "containers": [{"name": "c0",
                                 "resources": {"requests": dict(requests)}}]},
        "status": {"phase": "Pending"},
    }


def selector(match_labels: dict) -> dict:
    return {"matchLabels": dict(match_labels)}


def spread(max_skew: int, key: str, when: str, match_labels: dict) -> dict:
    return {"maxSkew": max_skew, "topologyKey": key,
            "whenUnsatisfiable": when,
            "labelSelector": selector(match_labels)}


def uniform_nodes(n: int) -> list:
    """32 CPU / 128 Gi / 110 pods, ten zones round-robin."""
    return [node(f"node-{i}", {"cpu": "32", "memory": "128Gi", "pods": "110"},
                 {ZONE: ZONES[i % len(ZONES)]}) for i in range(n)]
