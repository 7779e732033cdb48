"""One compile a pod template, not one a pod (PR 38): ``SnapshotEncoder``
keeps the compiled record and the row pack of the first pod of a template
and hands every later pod equal under the template key a shallow copy, with
the pod's own ``requests`` vector. The ``PodBatch`` that comes out must
equal, leaf for leaf (dtype, shape, bytes), what an encoder gives that never
consults the store — the same encoder code with ``cache_rows=False``, which
compiles and packs every pod for itself.

Subjects: the generators of the four listed cells at reduced size and a
seeded fuzz over every field the key covers. Routes: every pod precompiled at
event time, the loop's miss path alone (cold pods), the two mixed (the
informer found the encode lock busy for every other pod), and the same pods
again after a wider pod promoted the row signature."""

import copy
import random
import threading
import weakref

import jax
import numpy as np
import pytest

from kubernetes_tpu.api import Node, Pod
from kubernetes_tpu.encode import snapshot
from kubernetes_tpu.encode.snapshot import _TEMPLATE_CAP, SnapshotEncoder
from kubernetes_tpu.metrics.registry import REGISTRY
from kubernetes_tpu.sched.cache import SchedulerCache

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
NAMESPACES = {"default": {}, "team-a": {"tier": "prod"},
              "team-b": {"tier": "dev"}, "sched-0": {}, "sched-1": {}}
SERIES = 'scheduler_encode_pod_template_total{result="%s"}'

CELLS = ("mixed", "antiaffinity", "topologyspread", "unschedulable")
FUZZ = tuple(f"fuzz-{seed}" for seed in range(4))
ROUTES = ("event", "loop", "lock_busy", "promoted")


# ---- subjects -------------------------------------------------------------

def _container(name="c0", requests=None, **extra) -> dict:
    return {"name": name, "resources": {"requests": requests or {
        "cpu": "250m", "memory": "256Mi"}}, **extra}


def _pod(name: str, labels: dict, containers: list, namespace="default",
         **spec) -> dict:
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": namespace,
                         "uid": f"uid-{name}", "resourceVersion": "1",
                         "labels": labels},
            "spec": {"containers": containers, **spec},
            "status": {"phase": "Pending"}}


def rich_pod(name: str = "rich") -> dict:
    """Populates every group a volume-less pod can, two containers, every
    kind of term: the subject of the key's mutants."""
    term = {"topologyKey": ZONE,
            "labelSelector": {"matchLabels": {"app": "db"}},
            "namespaces": ["team-a", "team-b"]}
    by_selector = {"topologyKey": HOSTNAME,
                   "labelSelector": {"matchExpressions": [
                       {"key": "app", "operator": "In",
                        "values": ["web", "db"]}]},
                   "namespaceSelector": {"matchLabels": {"tier": "prod"}},
                   "matchLabelKeys": ["tier"]}
    return _pod(
        name, {"app": "web", "tier": "front"},
        [_container(image="registry.local/web:1.2",
                    ports=[{"containerPort": 8080, "hostPort": 8080,
                            "protocol": "TCP"}]),
         _container("c1", image="registry.local/side:3")],
        priority=7,
        tolerations=[{"key": "dedicated", "operator": "Equal",
                      "value": "infra", "effect": "NoSchedule"}],
        nodeSelector={"disk": "ssd"},
        affinity={
            "nodeAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": {
                    "nodeSelectorTerms": [{"matchExpressions": [
                        {"key": ZONE, "operator": "In",
                         "values": ["zone-0", "zone-1"]}]}]},
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 30, "preference": {"matchExpressions": [
                        {"key": "disk", "operator": "Exists"}]}}]},
            "podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [term],
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 40, "podAffinityTerm": by_selector}]},
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    dict(term, topologyKey=HOSTNAME)],
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 20, "podAffinityTerm": dict(
                        term, topologyKey=HOSTNAME)}]}},
        topologySpreadConstraints=[
            {"maxSkew": 2, "topologyKey": ZONE, "minDomains": 3,
             "whenUnsatisfiable": "DoNotSchedule",
             "labelSelector": {"matchLabels": {"app": "web"}},
             "matchLabelKeys": ["tier"]}],
        resourceClaims=[{"name": "gpu", "resourceClaimName": "gpu-0"}])


def wide_pod(name: str = "wide") -> dict:
    """Wider than any subject in every group it carries, so that encoding
    it promotes the row signature."""
    exprs = [{"key": k, "operator": "In", "values": ["a", "b", "c", "d"]}
             for k in ("rack", "row", "hall", "site")]
    anti = [{"topologyKey": HOSTNAME, "namespaces": ["n1", "n2", "n3"],
             "labelSelector": {"matchExpressions": exprs}}] * 3
    return _pod(
        name, {"app": "wide"},
        [_container(f"c{i}", image=f"registry.local/wide:{i}", ports=[
            {"containerPort": 9000 + i, "hostPort": 9000 + i}])
         for i in range(3)],
        tolerations=[{"key": f"k{i}", "operator": "Exists"}
                     for i in range(5)],
        nodeSelector={"disk": "ssd", "rack": "a", "row": "b"},
        affinity={
            "nodeAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": {
                    "nodeSelectorTerms": [{"matchExpressions": exprs}] * 3},
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": w, "preference": {"matchExpressions": exprs}}
                    for w in (1, 2, 3)]},
            "podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": anti,
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 5, "podAffinityTerm": t} for t in anti]},
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": anti}},
        topologySpreadConstraints=[
            {"maxSkew": 1, "topologyKey": k,
             "whenUnsatisfiable": "ScheduleAnyway",
             "labelSelector": {"matchExpressions": exprs}}
            for k in (ZONE, HOSTNAME, "rack")])


def fuzz_pods(seed: int) -> list:
    """A dozen templates drawn field by field over everything the key
    covers, one to six replicas each that differ in name, uid,
    resourceVersion, requests, initContainers, overhead and (some)
    ``nodeName``, shuffled."""
    rng = random.Random(seed)

    def selector():
        if rng.random() < 0.5:
            return {"matchLabels": {"app": rng.choice(["web", "db"])}}
        return {"matchExpressions": [
            {"key": rng.choice(["app", "tier"]),
             "operator": rng.choice(["In", "NotIn"]),
             "values": rng.sample(["web", "db", "cache"], rng.randint(1, 2))},
            {"key": "tier", "operator": "Exists"}][:rng.randint(1, 2)]}

    def pod_term():
        t = {"topologyKey": rng.choice([ZONE, HOSTNAME]),
             "labelSelector": selector()}
        r = rng.random()
        if r < 0.3:
            t["namespaces"] = rng.sample(["team-a", "team-b", "default"],
                                         rng.randint(1, 2))
        elif r < 0.5:
            t["namespaceSelector"] = {"matchLabels": {
                "tier": rng.choice(["prod", "dev"])}}
        if rng.random() < 0.3:
            t["matchLabelKeys"] = ["tier"]
        return t

    def node_term():
        return {"matchExpressions": [
            {"key": rng.choice([ZONE, "disk"]),
             "operator": rng.choice(["In", "NotIn", "Exists"]),
             "values": rng.sample(["zone-0", "zone-1", "ssd"],
                                  rng.randint(1, 2))}]}

    def template():
        spec: dict = {}
        labels = {"app": rng.choice(["web", "db", "cache"])}
        if rng.random() < 0.5:
            labels["tier"] = rng.choice(["front", "back"])
        containers = [{"name": f"c{i}"} for i in range(rng.randint(1, 2))]
        for c in containers:
            if rng.random() < 0.6:
                c["image"] = f"registry.local/{rng.choice('abc')}:1"
            if rng.random() < 0.2:
                port = rng.choice([8080, 8443])
                c["ports"] = [{"containerPort": port, "hostPort": port,
                               "protocol": rng.choice(["TCP", "UDP"])}]
        if rng.random() < 0.3:
            spec["priority"] = rng.choice([0, 5, 100])
        if rng.random() < 0.3:
            spec["tolerations"] = [
                {"key": "dedicated", "operator": "Equal",
                 "value": rng.choice(["infra", "batch"]),
                 "effect": rng.choice(["NoSchedule", "NoExecute"])}
            ] + [{"key": "spot", "operator": "Exists"}][:rng.randint(0, 1)]
        if rng.random() < 0.3:
            spec["nodeSelector"] = {"disk": rng.choice(["ssd", "hdd"])}
        aff: dict = {}
        if rng.random() < 0.3:
            na = aff["nodeAffinity"] = {}
            if rng.random() < 0.6:
                na["requiredDuringSchedulingIgnoredDuringExecution"] = {
                    "nodeSelectorTerms": [node_term() for _ in range(
                        rng.randint(1, 2))]}
            if rng.random() < 0.6:
                na["preferredDuringSchedulingIgnoredDuringExecution"] = [
                    {"weight": rng.choice([10, 50]),
                     "preference": node_term()}]
        for kind in ("podAffinity", "podAntiAffinity"):
            if rng.random() < 0.3:
                a = aff[kind] = {}
                if rng.random() < 0.6:
                    a["requiredDuringSchedulingIgnoredDuringExecution"] = [
                        pod_term() for _ in range(rng.randint(1, 2))]
                if rng.random() < 0.6:
                    a["preferredDuringSchedulingIgnoredDuringExecution"] = [
                        {"weight": rng.choice([20, 80]),
                         "podAffinityTerm": pod_term()}]
        if aff:
            spec["affinity"] = aff
        if rng.random() < 0.4:
            spec["topologySpreadConstraints"] = [
                {"maxSkew": rng.choice([1, 2, 5]),
                 "topologyKey": rng.choice([ZONE, HOSTNAME]),
                 "whenUnsatisfiable": rng.choice(
                     ["DoNotSchedule", "ScheduleAnyway"]),
                 "labelSelector": selector(),
                 **({"minDomains": 2} if rng.random() < 0.3 else {}),
                 **({"nodeTaintsPolicy": "Honor"}
                    if rng.random() < 0.3 else {}),
                 **({"matchLabelKeys": ["tier"]}
                    if rng.random() < 0.3 else {})}
                for _ in range(rng.randint(1, 2))]
        return (rng.choice(["default", "team-a", "team-b"]), labels,
                containers, spec)

    pods = []
    for t in range(12):
        namespace, labels, containers, spec = template()
        for r in range(rng.randint(1, 6)):
            cs = copy.deepcopy(containers)
            for c in cs:
                c["resources"] = {"requests": {
                    "cpu": rng.choice(["100m", "250m", "1"]),
                    "memory": rng.choice(["128Mi", "1Gi"])}}
            extra = {}
            if rng.random() < 0.2:
                extra["initContainers"] = [_container(
                    "init", {"cpu": rng.choice(["2", "3"])})]
            if rng.random() < 0.2:
                extra["overhead"] = {"cpu": "50m"}
            if rng.random() < 0.15:
                extra["nodeName"] = f"node-{rng.randint(0, 20)}"
            pods.append(_pod(f"t{t}-r{r}", dict(labels), cs, namespace,
                             **copy.deepcopy(spec), **extra))
    rng.shuffle(pods)
    return pods


def _subject(name: str):
    """-> (Node objects, pod dicts) of one subject."""
    from yardstick.generators import (_objects, mixed_heterogeneous,
                                      upstream_pod_anti_affinity,
                                      upstream_topology_spreading,
                                      upstream_unschedulable)
    if name == "mixed":
        nodes, pods = mixed_heterogeneous.generate(38, 16, 360)
    elif name == "antiaffinity":
        nodes, pods = upstream_pod_anti_affinity.generate(38, 16, 48)
        for p in pods:
            p["metadata"]["namespace"] = "sched-1"
    elif name == "topologyspread":
        nodes, pods = upstream_topology_spreading.build(16, 32, 16)
    elif name == "unschedulable":
        nodes, phases = upstream_unschedulable.generate_phases(
            38, 16, {"warmup": 8, "pending": 16, "measure": 40})
        pods = phases["warmup"] + phases["pending"] + phases["measure"]
    else:
        nodes = _objects.uniform_nodes(24)
        pods = fuzz_pods(int(name.split("-")[1]))
    return [Node.from_dict(n) for n in nodes], pods


def _encoder(nodes, dicts):
    enc = SnapshotEncoder()
    enc.set_namespaces(NAMESPACES)
    _ct, meta = enc.encode_cluster(
        nodes, [], [Pod.from_dict(d) for d in dicts])
    return enc, meta


def assert_same_batch(got, want):
    """Leaf for leaf: dtype, shape, bytes."""
    g, gdef = jax.tree_util.tree_flatten_with_path(got)
    x, xdef = jax.tree_util.tree_flatten_with_path(want)
    assert gdef == xdef and len(g) == 89
    for (path, a), (_p, b) in zip(g, x):
        name = jax.tree_util.keystr(path)
        assert isinstance(a, np.ndarray), name
        assert a.dtype == b.dtype, (name, a.dtype, b.dtype)
        assert a.shape == b.shape, (name, a.shape, b.shape)
        assert a.tobytes() == b.tobytes(), name


def offered(enc) -> tuple:
    return (enc.pod_template_hits, enc.pod_template_misses,
            enc.pod_template_bypass + enc.pod_template_lock_busy)


# ---- (i) the equivalence, one case a (subject, route) pair ----------------

@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("subject", CELLS + FUZZ)
def test_batch_through_the_store_equals_the_batch_without_it(subject, route):
    nodes, dicts = _subject(subject)
    enc, meta = _encoder(nodes, dicts)      # consults the store
    ref, ref_meta = _encoder(nodes, dicts)  # never does: cache_rows=False
    P = 512
    templates = len({enc._template_key(p, ()) for p in map(
        Pod.from_dict, dicts)})
    assert templates < len(dicts)  # the subject has replicas to reuse

    def both(ds):
        pods = [Pod.from_dict(d) for d in ds]
        was = offered(ref)
        want = ref.encode_pods([Pod.from_dict(d) for d in ds], ref_meta,
                               min_p=P, cache_rows=False)
        assert offered(ref) == (was[0], was[1], was[2] + len(ds))
        return pods, want

    def encode(pods, want):
        assert_same_batch(enc.encode_pods(pods, meta, min_p=P), want)
        assert enc._row_sig == ref._row_sig

    if route in ("event", "lock_busy", "promoted"):
        # the signature as a warm-up drain leaves it, on other Pod objects
        # than the watch will deliver
        encode(*both(dicts))
    pods, want = both(dicts)
    start = offered(enc)
    if route == "event":
        for p in pods:
            assert enc.precompile_pod(p)
        # every template was filled by the warm-up: nothing is built
        assert offered(enc) == (start[0] + len(pods), start[1], start[2])
        encode(pods, want)
        assert offered(enc) == (start[0] + len(pods), start[1], start[2])
    elif route == "loop":
        assert start == (0, 0, 0)
        encode(pods, want)
        assert offered(enc) == (len(pods) - templates, templates, 0)
        # the same objects again (a failure's re-pop) are not offered again
        encode(pods, want)
        assert offered(enc) == (len(pods) - templates, templates, 0)
    elif route == "lock_busy":
        for p in pods[::2]:
            enc.precompile_pod(p)
        encode(pods, want)
        assert offered(enc) == (start[0] + len(pods), start[1], start[2])
    else:
        for p in pods:
            enc.precompile_pod(p)
        sig = enc._row_sig
        encode(*both([wide_pod()]))
        assert enc._row_sig != sig
        _, want = both(dicts)  # the reference at the promoted widths
        # every cached pack is stale: rebuilt once a template, not a pod
        start = offered(enc)
        encode(pods, want)
        assert offered(enc) == (start[0] + len(pods) - templates,
                                start[1] + templates, start[2])
        was = enc.pod_rows_stacked
        encode(pods, want)
        assert enc.pod_rows_stacked - was == len(pods)


# ---- (ii) the key ---------------------------------------------------------

def _set(path: str, value):
    def mutate(d):
        node = d
        *head, last = path.split("/")
        for part in head:
            node = node[int(part) if part.isdigit() else part]
        node[int(last) if last.isdigit() else last] = value
    return mutate


_AFF = "spec/affinity/"
_REQ = "/requiredDuringSchedulingIgnoredDuringExecution"
_PREF = "/preferredDuringSchedulingIgnoredDuringExecution"
READ = {  # one mutant a field that _compile_pod or _build_rows reads
    "namespace": _set("metadata/namespace", "team-a"),
    "label_value": _set("metadata/labels/tier", "back"),
    "label_added": _set("metadata/labels/extra", "x"),
    "priority": _set("spec/priority", 8),
    "toleration_value": _set("spec/tolerations/0/value", "batch"),
    "toleration_effect": _set("spec/tolerations/0/effect", "NoExecute"),
    "toleration_operator": _set("spec/tolerations/0/operator", "Exists"),
    "node_selector": _set("spec/nodeSelector/disk", "hdd"),
    "node_affinity_value": _set(
        _AFF + "nodeAffinity" + _REQ
        + "/nodeSelectorTerms/0/matchExpressions/0/values/1", "zone-2"),
    "node_affinity_operator": _set(
        _AFF + "nodeAffinity" + _REQ
        + "/nodeSelectorTerms/0/matchExpressions/0/operator", "NotIn"),
    "preferred_weight": _set(_AFF + "nodeAffinity" + _PREF + "/0/weight", 31),
    "affinity_term_namespaces": _set(
        _AFF + "podAffinity" + _REQ + "/0/namespaces", ["team-a"]),
    "affinity_term_topology": _set(
        _AFF + "podAffinity" + _REQ + "/0/topologyKey", HOSTNAME),
    "affinity_namespace_selector": _set(
        _AFF + "podAffinity" + _PREF
        + "/0/podAffinityTerm/namespaceSelector/matchLabels/tier", "dev"),
    "affinity_match_label_keys": _set(
        _AFF + "podAffinity" + _PREF
        + "/0/podAffinityTerm/matchLabelKeys", []),
    "anti_selector": _set(
        _AFF + "podAntiAffinity" + _REQ
        + "/0/labelSelector/matchLabels/app", "web"),
    "anti_preferred_weight": _set(
        _AFF + "podAntiAffinity" + _PREF + "/0/weight", 21),
    "spread_max_skew": _set("spec/topologySpreadConstraints/0/maxSkew", 3),
    "spread_min_domains": _set(
        "spec/topologySpreadConstraints/0/minDomains", 4),
    "spread_when": _set(
        "spec/topologySpreadConstraints/0/whenUnsatisfiable",
        "ScheduleAnyway"),
    "spread_taints_policy": _set(
        "spec/topologySpreadConstraints/0/nodeTaintsPolicy", "Honor"),
    "spread_affinity_policy": _set(
        "spec/topologySpreadConstraints/0/nodeAffinityPolicy", "Ignore"),
    "image": _set("spec/containers/0/image", "registry.local/web:1.3"),
    "second_image": _set("spec/containers/1/image", "registry.local/side:4"),
    "host_port": _set("spec/containers/0/ports/0/hostPort", 8081),
    "host_ip": _set("spec/containers/0/ports/0/hostIP", "10.0.0.1"),
    "port_protocol": _set("spec/containers/0/ports/0/protocol", "UDP"),
    "resource_claim": _set("spec/resourceClaims/0/resourceClaimName",
                           "gpu-1"),
}
NOT_READ = {  # what differs between a template's replicas
    "name": _set("metadata/name", "rich-2"),
    "uid": _set("metadata/uid", "uid-2"),
    "resource_version": _set("metadata/resourceVersion", "99"),
    "annotation": _set("metadata/annotations", {"seen": "yes"}),
    "owner": _set("metadata/ownerReferences", [{"uid": "rs-2"}]),
    "requests": _set("spec/containers/0/resources/requests/cpu", "2"),
    "init_requests": _set("spec/initContainers",
                          [_container("init", {"cpu": "3"})]),
    "overhead": _set("spec/overhead", {"memory": "64Mi"}),
    "node_name": _set("spec/nodeName", "node-3"),
}


def _records(enc, p):
    """What a pod's compile and pack come to, comparable: the record less
    its pod, the pack less its requests, arrays as (dtype, shape, bytes)."""
    c = enc._compile_pod(p)
    resources, K, NSB, w = enc._row_env
    pk = enc._build_rows(c, resources, K, NSB, w)
    return ({k: v for k, v in c.items() if k != "pod"},
            {k: ((v.dtype, v.shape, v.tobytes())
                 if isinstance(v, np.ndarray) else v)
             for k, v in pk.items() if k != "requests"})


@pytest.fixture(scope="module")
def keyed():
    from yardstick.generators._objects import uniform_nodes
    nodes = [Node.from_dict(n) for n in uniform_nodes(8)]
    enc, meta = _encoder(nodes, [rich_pod()])
    enc.encode_pods([Pod.from_dict(wide_pod())], meta)
    return enc


@pytest.mark.parametrize("field", sorted(READ))
def test_a_field_the_compile_reads_changes_the_key(keyed, field):
    base = Pod.from_dict(rich_pod())
    d = rich_pod()
    READ[field](d)
    mutant = Pod.from_dict(d)
    assert keyed._template_key(mutant, ()) != keyed._template_key(base, ())
    if field != "resource_claim":  # read through the DRA catalog only
        assert _records(keyed, mutant) != _records(keyed, base)


@pytest.mark.parametrize("field", sorted(NOT_READ))
def test_what_differs_between_replicas_leaves_the_key_alone(keyed, field):
    base = Pod.from_dict(rich_pod())
    d = rich_pod()
    NOT_READ[field](d)
    mutant = Pod.from_dict(d)
    assert keyed._template_key(mutant, ()) == keyed._template_key(base, ())
    # equal key, equal compile inputs: what the store would share is equal
    assert _records(keyed, mutant) == _records(keyed, base)
    epoch = keyed._epoch_for(base)
    assert keyed._template_key(base, epoch) != keyed._template_key(base, ())


def test_pods_differing_in_requests_share_a_template_and_not_a_row():
    from yardstick.generators._objects import uniform_nodes
    nodes = [Node.from_dict(n) for n in uniform_nodes(8)]
    small, big = rich_pod("small"), rich_pod("big")
    big["spec"]["containers"][0]["resources"]["requests"] = {
        "cpu": "3", "memory": "2Gi"}
    enc, meta = _encoder(nodes, [small, big])
    pods = [Pod.from_dict(small), Pod.from_dict(big)]
    batch = enc.encode_pods(pods, meta)
    assert len(enc._templates) == 1 and offered(enc) == (1, 1, 0)
    assert not np.array_equal(batch.requests[0], batch.requests[1])
    for i, p in enumerate(pods):
        assert np.array_equal(batch.requests[i],
                              enc._request_vector(p, meta.resources))
    # a request outside the signature's resource list: a record, no pack
    odd = rich_pod("odd")
    odd["spec"]["containers"][0]["resources"]["requests"][
        "example.com/widget"] = "1"
    p = Pod.from_dict(odd)
    assert enc.precompile_pod(p)
    ent = enc._pod_cache[p.key]
    assert ent[2]["pod"] is p and ent[3] is None and ent[4] is None
    assert offered(enc) == (2, 1, 0)


# ---- (iii) epochs and promotions ------------------------------------------

def test_an_epoch_bump_and_a_promotion_each_miss_once_a_template():
    nodes, dicts = _subject("mixed")
    enc, meta = _encoder(nodes, dicts)
    enc.encode_pods([Pod.from_dict(d) for d in dicts], meta)
    n = len(dicts)
    templates = len(enc._templates)
    assert 1 < templates < n
    assert offered(enc) == (n - templates, templates, 0)

    def event_time():
        was = offered(enc)
        for d in dicts:
            enc.precompile_pod(Pod.from_dict(d))
        now = offered(enc)
        return now[0] - was[0], now[1] - was[1]

    assert event_time() == (n, 0)
    enc.set_namespaces(NAMESPACES)  # a global bump: every record is stale
    assert event_time() == (n - templates, templates)
    assert event_time() == (n, 0)
    # a tenant's bump reaches that tenant's pods alone: none of these
    enc.set_namespaces(NAMESPACES, changed_tenants=["tenant-7"])
    assert event_time() == (n, 0)
    sig = enc._row_sig
    enc.encode_pods([Pod.from_dict(wide_pod())], meta)
    assert enc._row_sig != sig
    # the records stand, the packs are rebuilt: a miss, once a template
    assert event_time() == (n - templates, templates)
    assert event_time() == (n, 0)


# ---- (iv) what is shared ---------------------------------------------------

def test_a_hit_shares_read_only_arrays_and_never_the_pod():
    from yardstick.generators._objects import uniform_nodes
    nodes = [Node.from_dict(n) for n in uniform_nodes(8)]
    enc, meta = _encoder(nodes, [rich_pod()])
    enc.encode_pods([Pod.from_dict(rich_pod("warm"))], meta)
    a, b = Pod.from_dict(rich_pod("a")), Pod.from_dict(rich_pod("b"))
    groups = enc.row_groups_built, enc.row_groups_default
    enc.precompile_pod(a)
    enc.precompile_pod(b)
    # a pack copied from the template counts the groups it holds, as one
    # built does: encode_row_groups_built_share reads a pod's pack either way
    assert (enc.row_groups_built, enc.row_groups_default) == (
        groups[0] + 20, groups[1] + 4)
    (rec, sig, shared, groups), = enc._templates.values()
    assert "pod" not in rec and "requests" not in shared
    assert sig == enc._row_sig and groups == 10  # all but the volumes' two
    ea, eb = enc._pod_cache[a.key], enc._pod_cache[b.key]
    assert ea[5] is eb[5]
    assert ea[2] is not eb[2] and ea[4] is not eb[4]
    assert ea[2]["pod"] is a and eb[2]["pod"] is b
    for k, v in rec.items():
        assert ea[2][k] is v and eb[2][k] is v, k
    arrays = 0
    for k, v in shared.items():
        assert ea[4][k] is v and eb[4][k] is v, k
        if isinstance(v, np.ndarray):
            arrays += 1
            assert not v.flags.writeable, k
            with pytest.raises(ValueError):
                v[...] = 0
    assert arrays > 40  # every group a volume-less pod can populate
    assert ea[4]["requests"] is not eb[4]["requests"]
    assert ea[4]["requests"].flags.writeable
    # stacking the shared rows leaves a batch of its own, writeable
    batch = enc.encode_pods([a, b], meta)
    assert batch.pod_labels.flags.writeable


# ---- (v) the bound ----------------------------------------------------------

def test_all_distinct_pods_leave_the_store_at_its_cap():
    nodes, _ = _subject("unschedulable")
    enc, meta = _encoder(nodes, [])
    enc.encode_pods([Pod.from_dict(_pod("warm", {"app": "warm"},
                                        [_container()]))], meta)
    first = None
    for i in range(5000):
        # what a StatefulSet's or an indexed Job's pods carry
        p = Pod.from_dict(_pod(f"web-{i}", {
            "app": "web", "statefulset.kubernetes.io/pod-name": f"web-{i}"},
            [_container()]))
        first = first or enc._template_key(p, enc._epoch_for(p))
        enc.precompile_pod(p)
        enc.pod_cache_discard(p.key)
    assert _TEMPLATE_CAP < 5000
    assert len(enc._templates) == _TEMPLATE_CAP
    assert first not in enc._templates
    assert enc._template_key(p, enc._epoch_for(p)) in enc._templates
    assert offered(enc) == (0, 5001, 0)
    # a template in use stays young: it outlives a flood of one-off pods
    hot = [Pod.from_dict(plain) for plain in (
        _pod(f"hot-{i}", {"app": "hot"}, [_container()]) for i in range(3))]
    enc.precompile_pod(hot[0])
    for i in range(_TEMPLATE_CAP - 1):
        enc.precompile_pod(Pod.from_dict(_pod(
            f"one-{i}", {"app": "one", "index": str(i)}, [_container()])))
        enc.pod_cache_discard(f"default/one-{i}")
    enc.precompile_pod(hot[1])
    for i in range(_TEMPLATE_CAP - 1):
        enc.precompile_pod(Pod.from_dict(_pod(
            f"two-{i}", {"app": "two", "index": str(i)}, [_container()])))
        enc.pod_cache_discard(f"default/two-{i}")
    was = offered(enc)
    enc.precompile_pod(hot[2])
    assert offered(enc) == (was[0] + 1, was[1], was[2])
    assert len(enc._templates) == _TEMPLATE_CAP


# ---- (vi) the counter -------------------------------------------------------

def _series() -> dict:
    out = {}
    for line in REGISTRY.expose_text().splitlines():
        if line.startswith("scheduler_encode_pod_template_total{"):
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


def test_the_three_labels_read_zero_with_no_encoder(monkeypatch):
    assert set(_series()) == {SERIES % r for r in ("hit", "miss", "bypass")}
    monkeypatch.setattr(snapshot, "_ENCODERS", weakref.WeakSet())
    assert _series() == {SERIES % r: 0.0 for r in ("hit", "miss", "bypass")}


def test_the_three_labels_sum_to_the_pods_offered():
    nodes, dicts = _subject("mixed")
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    enc = cache._encoder
    _nodes, _ct, meta = cache.snapshot()
    before = _series()
    volume = _pod("vol", {"app": "vol"}, [_container()], volumes=[
        {"name": "data", "persistentVolumeClaim": {"claimName": "data"}}])
    event = [Pod.from_dict(d) for d in dicts[:200]]
    for p in event:
        cache.precompile_pod(p)
    cache.precompile_pod(Pod.from_dict(volume))          # bypass: volumes
    # the loop holds the encode lock: the informer does not wait, and the
    # pod meets the store on the loop's path instead
    skipped = Pod.from_dict(dicts[200])
    with cache._encode_lock:
        t = threading.Thread(target=cache.precompile_pod, args=(skipped,))
        t.start()
        t.join(10.0)
    assert enc.pod_template_lock_busy == 1 and skipped.key not in enc._pod_cache
    cold = [Pod.from_dict(d) for d in dicts[201:300]]
    cache.encode_pods(event + [skipped] + cold, meta)    # 100 more offered
    cache.encode_pods([Pod.from_dict(volume)], meta)     # bypass: volumes
    derived = [Pod.from_dict(d) for d in dicts[300:310]]
    cache.encode_pods(derived, meta, cache_rows=False)   # bypass, ten
    hits, misses, bypass = offered(enc)
    assert bypass == 1 + 1 + 1 + 10
    # the event pods met no row signature yet: offered once for the record
    # and once more, in the drain, for the pack
    assert hits + misses == 200 + 100 + 200
    assert hits > 100  # replicas among them
    rose = {k: v - before[k] for k, v in _series().items()}
    assert rose == {SERIES % "hit": hits, SERIES % "miss": misses,
                    SERIES % "bypass": bypass}


# ---- two threads, one store -------------------------------------------------

def test_informers_and_the_loop_share_the_store_without_losing_a_pod():
    """Three informer threads precompile while the loop encodes, on a
    shortened switch interval: every pod offered is counted once (a lost
    update of the lock-busy count would show), and every batch the loop
    encodes meanwhile equals the one an encoder without the store gives."""
    import sys
    import time
    nodes, dicts = _subject("mixed")
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    enc = cache._encoder
    _nodes, _ct, meta = cache.snapshot([Pod.from_dict(d) for d in dicts])
    ref = SnapshotEncoder()
    ref.node_headroom, ref.value_headroom, ref.ns_headroom = (
        enc.node_headroom, enc.value_headroom, enc.ns_headroom)
    _ct, ref_meta = ref.encode_cluster(
        nodes, [], [Pod.from_dict(d) for d in dicts])
    P = 512
    # every string interned, in one order, before the threads start
    cache.encode_pods([Pod.from_dict(d) for d in dicts], meta, min_p=P)
    loop_dicts = dicts[:128]
    ref.encode_pods([Pod.from_dict(d) for d in dicts], ref_meta, min_p=P,
                    cache_rows=False)  # the same sticky widths
    want = ref.encode_pods([Pod.from_dict(d) for d in loop_dicts], ref_meta,
                           min_p=P, cache_rows=False)
    assert enc._row_sig == ref._row_sig
    start = offered(enc)
    stop = time.monotonic() + 2.0
    calls = [0, 0, 0]
    errors = []

    def informer(i):
        try:
            while time.monotonic() < stop:
                for d in dicts[i::3]:
                    cache.precompile_pod(Pod.from_dict(d))
                    calls[i] += 1
        except Exception as e:  # surfaced below: a thread must not die silent
            errors.append(e)

    threads = [threading.Thread(target=informer, args=(i,)) for i in range(3)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    encoded = 0
    try:
        for t in threads:
            t.start()
        while time.monotonic() < stop:
            got = cache.encode_pods([Pod.from_dict(d) for d in loop_dicts],
                                    meta, min_p=P)
            assert_same_batch(got, want)
            encoded += len(loop_dicts)
    finally:
        sys.setswitchinterval(interval)
        for t in threads:
            t.join(30.0)
    assert not errors and not any(t.is_alive() for t in threads)
    assert encoded and all(calls)
    assert enc.pod_template_lock_busy > 0  # the two did meet at the lock
    now = offered(enc)
    assert sum(now) - sum(start) == sum(calls) + encoded
    assert now[1] == start[1]  # every template was there: nothing was built
