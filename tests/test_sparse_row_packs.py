"""Sparse pod row packs (PR 26): a pack holds only the constraint groups a
pod populates, and ``encode_pods`` writes a group's rows only for the pods
that carry it. The ``PodBatch`` that comes out must equal, leaf for leaf,
what the dense assembly gave — that assembly was removed from the program
and lives on below as the plain reference (``dense_rows``, ``dense_batch``):
every pod gets every field of every group at the bucket widths, every field
is one ``np.stack`` over all pods.

Subjects: every generator under ``yardstick/generators/`` and one hand-built
batch whose first pod populates all twelve groups. Routes: packs precompiled
at event time, the cold path, a re-pop that reuses the cached pack, and the
same pods again after a wider pod promoted the row signature."""

import importlib

import jax
import numpy as np
import pytest

from kubernetes_tpu.api import Node, Pod
from kubernetes_tpu.encode.snapshot import (_ROW_GROUPS, PodBatch,
                                            SelectorSet, SnapshotEncoder,
                                            TermSet, _selset_arrays,
                                            _selset_fill)
from kubernetes_tpu.metrics.registry import REGISTRY
from kubernetes_tpu.sched.volumebinding import VolumeCatalog

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"

GENERATORS = ("mixed_heterogeneous", "upstream_pod_anti_affinity",
              "pod_anti_affinity", "preferred_topology_spreading",
              "noderesources_fit", "scheduling_basic")
ROUTES = ("event", "cold", "repop", "promoted")


# ---- the plain reference: the dense assembly as the program had it --------

def dense_rows(enc, c, resources, K, NSB, w) -> dict:
    """ONE pod's rows, every field of every group, at the bucket widths."""
    X, VV, AX, AV = w["X"], w["VV"], w["AX"], w["AV"]
    p = c["pod"]
    rows: dict = {
        "priority": int(p.spec.priority), "ns": int(c["ns"]),
        "attach_req": int(c["attach_req"]),
    }
    rows["requests"] = enc._request_vector(p, resources)
    labels = np.full(K, -1, np.int32)
    for kid, vid in c["labels"].items():
        labels[kid] = vid
    rows["labels"] = labels

    tol_key = np.full(w["TOL"], -1, np.int32)
    tol_op = np.zeros(w["TOL"], np.int32)
    tol_val = np.full(w["TOL"], -1, np.int32)
    tol_effect = np.full(w["TOL"], -1, np.int32)
    tol_valid = np.zeros(w["TOL"], bool)
    for t_idx, (kid, opc, vid, eff) in enumerate(c["tols"]):
        tol_key[t_idx], tol_op[t_idx] = kid, opc
        tol_val[t_idx], tol_effect[t_idx] = vid, eff
        tol_valid[t_idx] = True
    rows.update(tol_key=tol_key, tol_op=tol_op, tol_val=tol_val,
                tol_effect=tol_effect, tol_valid=tol_valid)

    sel_key = np.full(w["S"], -1, np.int32)
    sel_val = np.full(w["S"], -1, np.int32)
    sel_valid = np.zeros(w["S"], bool)
    for s_idx, (kid, vid) in enumerate(c["sel"]):
        sel_key[s_idx], sel_val[s_idx] = kid, vid
        sel_valid[s_idx] = True
    rows.update(sel_key=sel_key, sel_val=sel_val, sel_valid=sel_valid)

    def termset_rows(prefix, T, terms):
        a = dict(
            key=np.full((T, X), -1, np.int32),
            op=np.zeros((T, X), np.int32),
            vals=np.full((T, X, VV), -1, np.int32),
            num=np.full((T, X), np.nan, np.float32),
            expr_valid=np.zeros((T, X), bool),
            term_valid=np.zeros(T, bool),
            weight=np.zeros(T, np.float32),
        )
        for t_idx, (weight, exprs) in enumerate(terms):
            a["term_valid"][t_idx] = True
            a["weight"][t_idx] = weight
            for x_idx, (kid, opc, vals, num) in enumerate(exprs):
                a["key"][t_idx, x_idx] = kid
                a["op"][t_idx, x_idx] = opc
                a["num"][t_idx, x_idx] = num
                a["expr_valid"][t_idx, x_idx] = True
                for v_idx, v in enumerate(vals):
                    a["vals"][t_idx, x_idx, v_idx] = v
        for f, arr in a.items():
            rows[f"{prefix}_{f}"] = arr
        rows[f"{prefix}_has_any"] = len(terms) > 0

    vol_terms = [(float(g), e) for g, e in c["vol_terms"]]
    termset_rows("req", w["TREQ"], c["req_terms"])
    termset_rows("pref", w["TPREF"], c["pref_terms"])
    termset_rows("vol", w["VT"], vol_terms)
    vol_group = np.full(w["VT"], -1, np.int32)
    for t_idx, (g, _e) in enumerate(c["vol_terms"]):
        vol_group[t_idx] = g
    vol_group_valid = np.zeros(w["VG"], bool)
    vol_group_valid[:c["vol_groups"]] = True
    rwo_pv = np.full(w["VB"], -1, np.int32)
    rwo_valid = np.zeros(w["VB"], bool)
    for b_idx, pvid in enumerate(c["vol_rwo"]):
        rwo_pv[b_idx] = pvid
        rwo_valid[b_idx] = True
    rows.update(vol_group=vol_group, vol_group_valid=vol_group_valid,
                rwo_pv=rwo_pv, rwo_valid=rwo_valid)

    port_proto = np.full(w["PP"], -1, np.int32)
    port_port = np.full(w["PP"], -1, np.int32)
    port_ip = np.full(w["PP"], -1, np.int32)
    port_valid = np.zeros(w["PP"], bool)
    for pt_idx, (proto, port, ip) in enumerate(c["ports"]):
        port_proto[pt_idx], port_port[pt_idx] = proto, port
        port_ip[pt_idx] = ip
        port_valid[pt_idx] = True
    rows.update(port_proto=port_proto, port_port=port_port,
                port_ip=port_ip, port_valid=port_valid)

    images = np.full(w["CI"], -1, np.int32)
    for ci_idx, img in enumerate(c["images"]):
        images[ci_idx] = img
    rows["images"] = images

    def selset_rows(prefix, T, items, scalars):
        a = _selset_arrays((T,), AX, AV)
        topo = np.full(T, -1, np.int32)
        valid = np.zeros(T, bool)
        ns_explicit = np.zeros(T, bool)
        ns_mask = np.zeros((T, NSB), bool)
        extra_arrs = {nm: np.full(T, dflt, dt) for nm, dt, dflt in scalars}
        for t_idx, item in enumerate(items):
            tk, sv, exprs = item[0], item[1], item[2]
            ns_ids = item[-1]
            topo[t_idx] = tk
            valid[t_idx] = True
            _selset_fill(a, (t_idx,), sv, exprs)
            for (nm, _dt, _df), val in zip(scalars, item[3:-1]):
                extra_arrs[nm][t_idx] = val
            if ns_ids is not None:
                ns_explicit[t_idx] = True
                for nid in ns_ids:
                    ns_mask[t_idx, nid] = True
        for f, arr in a.items():
            rows[f"{prefix}_sel_{f}"] = arr
        rows[f"{prefix}_topo"] = topo
        rows[f"{prefix}_valid"] = valid
        rows[f"{prefix}_ns_explicit"] = ns_explicit
        rows[f"{prefix}_ns_mask"] = ns_mask
        for nm, arr in extra_arrs.items():
            rows[f"{prefix}_{nm}"] = arr

    selset_rows("aff", w["AT"], c["aff_req"], [])
    selset_rows("anti", w["BT"], c["anti_req"], [])
    selset_rows("paff", w["CT"], c["paff"], [("weight", np.float32, 0.0)])
    selset_rows("sc", w["SC"], [t + (None,) for t in c["spreads"]],
                [("maxskew", np.int32, 1), ("hard", bool, False),
                 ("min_domains", np.int32, 0),
                 ("honor_affinity", bool, False),
                 ("honor_taints", bool, False)])
    return rows


def dense_batch(enc, pods, meta, P) -> PodBatch:
    """The batch the dense assembly gave for ``pods`` at the signature the
    encoder's last ``encode_pods`` left (``_row_env``): default-filled
    arrays, every field overwritten by one stack over ALL the pods' dense
    rows. Shares ``_compile_pod`` and the width pass with the program (out
    of this PR's scope, and idempotent here: every string is interned)."""
    resources, K, NSB, w = enc._row_env
    compiled = [enc._compile_pod(p) for p in pods]
    packs = [dense_rows(enc, c, resources, K, NSB, w) for c in compiled]
    n, R = len(pods), len(resources)
    X, VV, AX, AV = w["X"], w["VV"], w["AX"], w["AV"]
    TREQ, TPREF, VT, VG, VB = w["TREQ"], w["TPREF"], w["VT"], w["VG"], w["VB"]
    S, TOL, PP, CI = w["S"], w["TOL"], w["PP"], w["CI"]
    AT, BT, CT, SC = w["AT"], w["BT"], w["CT"], w["SC"]

    def _new_termset(T):
        return dict(
            key=np.full((P, T, X), -1, np.int32),
            op=np.zeros((P, T, X), np.int32),
            vals=np.full((P, T, X, VV), -1, np.int32),
            num=np.full((P, T, X), np.nan, np.float32),
            expr_valid=np.zeros((P, T, X), bool),
            term_valid=np.zeros((P, T), bool),
            weight=np.zeros((P, T), np.float32),
            has_any=np.zeros(P, bool),
        )

    req_a = _new_termset(TREQ)
    pref_a = _new_termset(TPREF)
    vol_a = _new_termset(VT)
    vol_group = np.full((P, VT), -1, np.int32)
    vol_group_valid = np.zeros((P, VG), bool)
    rwo_pv = np.full((P, VB), -1, np.int32)
    rwo_valid = np.zeros((P, VB), bool)
    attach_req = np.zeros(P, np.int32)

    def _new_selset(shape_prefix):
        return _selset_arrays(shape_prefix, AX, AV)

    requests = np.zeros((P, R), np.int32)
    pod_valid = np.zeros(P, bool)
    priority = np.zeros(P, np.int32)
    forced_node = np.full(P, -1, np.int32)
    pod_ns = np.full(P, -1, np.int32)
    pod_labels = np.full((P, K), -1, np.int32)
    tol_key = np.full((P, TOL), -1, np.int32)
    tol_op = np.zeros((P, TOL), np.int32)
    tol_val = np.full((P, TOL), -1, np.int32)
    tol_effect = np.full((P, TOL), -1, np.int32)
    tol_valid = np.zeros((P, TOL), bool)
    sel_key = np.full((P, S), -1, np.int32)
    sel_val = np.full((P, S), -1, np.int32)
    sel_valid = np.zeros((P, S), bool)
    pport_proto = np.full((P, PP), -1, np.int32)
    pport_port = np.full((P, PP), -1, np.int32)
    pport_ip = np.full((P, PP), -1, np.int32)
    pport_valid = np.zeros((P, PP), bool)
    pod_images = np.full((P, CI), -1, np.int32)
    image_bytes = np.zeros(P, np.float32)
    aff_sel = _new_selset((P, AT))
    aff_topo = np.full((P, AT), -1, np.int32)
    aff_valid = np.zeros((P, AT), bool)
    aff_ns_explicit = np.zeros((P, AT), bool)
    aff_ns_mask = np.zeros((P, AT, NSB), bool)
    anti_sel = _new_selset((P, BT))
    anti_topo = np.full((P, BT), -1, np.int32)
    anti_valid = np.zeros((P, BT), bool)
    anti_ns_explicit = np.zeros((P, BT), bool)
    anti_ns_mask = np.zeros((P, BT, NSB), bool)
    paff_sel = _new_selset((P, CT))
    paff_topo = np.full((P, CT), -1, np.int32)
    paff_weight = np.zeros((P, CT), np.float32)
    paff_valid = np.zeros((P, CT), bool)
    paff_ns_explicit = np.zeros((P, CT), bool)
    paff_ns_mask = np.zeros((P, CT, NSB), bool)
    sc_sel = _new_selset((P, SC))
    sc_topo = np.full((P, SC), -1, np.int32)
    sc_maxskew = np.ones((P, SC), np.int32)
    sc_hard = np.zeros((P, SC), bool)
    sc_valid = np.zeros((P, SC), bool)
    sc_min_domains = np.zeros((P, SC), np.int32)
    sc_honor_affinity = np.zeros((P, SC), bool)
    sc_honor_taints = np.zeros((P, SC), bool)

    if n:
        def put(dst, key):
            dst[:n] = np.stack([pk[key] for pk in packs])

        def put_scalar(dst, key, dtype):
            dst[:n] = np.fromiter((pk[key] for pk in packs), dtype, n)

        pod_valid[:n] = True
        # the two scalars a pack never froze (no DRA in these subjects)
        forced_node[:n] = [
            meta.node_index.get(p.spec.node_name, -2)
            if p.spec.node_name else -1 for p in pods]
        image_bytes[:n] = [
            float(sum(enc._image_sizes[im] for im in c["images"]))
            for c in compiled]
        put(requests, "requests")
        put_scalar(priority, "priority", np.int32)
        put_scalar(pod_ns, "ns", np.int32)
        put_scalar(attach_req, "attach_req", np.int32)
        put(pod_labels, "labels")
        for dst, f in ((tol_key, "tol_key"), (tol_op, "tol_op"),
                       (tol_val, "tol_val"), (tol_effect, "tol_effect"),
                       (tol_valid, "tol_valid")):
            put(dst, f)
        put(sel_key, "sel_key")
        put(sel_val, "sel_val")
        put(sel_valid, "sel_valid")
        for prefix, arrs in (("req", req_a), ("pref", pref_a),
                             ("vol", vol_a)):
            for f in ("key", "op", "vals", "num", "expr_valid",
                      "term_valid", "weight"):
                put(arrs[f], f"{prefix}_{f}")
            put_scalar(arrs["has_any"], f"{prefix}_has_any", bool)
        put(vol_group, "vol_group")
        put(vol_group_valid, "vol_group_valid")
        put(rwo_pv, "rwo_pv")
        put(rwo_valid, "rwo_valid")
        put(pport_proto, "port_proto")
        put(pport_port, "port_port")
        put(pport_ip, "port_ip")
        put(pport_valid, "port_valid")
        put(pod_images, "images")
        for prefix, selset, extras in (
                ("aff", aff_sel,
                 ((aff_topo, "topo"), (aff_valid, "valid"),
                  (aff_ns_explicit, "ns_explicit"),
                  (aff_ns_mask, "ns_mask"))),
                ("anti", anti_sel,
                 ((anti_topo, "topo"), (anti_valid, "valid"),
                  (anti_ns_explicit, "ns_explicit"),
                  (anti_ns_mask, "ns_mask"))),
                ("paff", paff_sel,
                 ((paff_topo, "topo"), (paff_valid, "valid"),
                  (paff_weight, "weight"),
                  (paff_ns_explicit, "ns_explicit"),
                  (paff_ns_mask, "ns_mask"))),
                ("sc", sc_sel,
                 ((sc_topo, "topo"), (sc_valid, "valid"),
                  (sc_maxskew, "maxskew"), (sc_hard, "hard"),
                  (sc_min_domains, "min_domains"),
                  (sc_honor_affinity, "honor_affinity"),
                  (sc_honor_taints, "honor_taints")))):
            for f in ("key", "op", "vals", "expr_valid", "valid"):
                put(selset[f], f"{prefix}_sel_{f}")
            for dst, f in extras:
                put(dst, f"{prefix}_{f}")

    return PodBatch(
        requests=requests, pod_valid=pod_valid, priority=priority,
        forced_node=forced_node, pod_ns=pod_ns, pod_labels=pod_labels,
        tol_key=tol_key, tol_op=tol_op, tol_val=tol_val,
        tol_effect=tol_effect, tol_valid=tol_valid,
        sel_key=sel_key, sel_val=sel_val, sel_valid=sel_valid,
        req_terms=TermSet(**req_a), pref_terms=TermSet(**pref_a),
        port_proto=pport_proto, port_port=pport_port, port_ip=pport_ip,
        port_valid=pport_valid,
        pod_images=pod_images, image_bytes=image_bytes,
        aff_sel=SelectorSet(**aff_sel), aff_topo=aff_topo,
        aff_valid=aff_valid, aff_ns_explicit=aff_ns_explicit,
        aff_ns_mask=aff_ns_mask,
        anti_sel=SelectorSet(**anti_sel), anti_topo=anti_topo,
        anti_valid=anti_valid, anti_ns_explicit=anti_ns_explicit,
        anti_ns_mask=anti_ns_mask,
        paff_sel=SelectorSet(**paff_sel), paff_topo=paff_topo,
        paff_weight=paff_weight, paff_valid=paff_valid,
        paff_ns_explicit=paff_ns_explicit, paff_ns_mask=paff_ns_mask,
        sc_sel=SelectorSet(**sc_sel), sc_topo=sc_topo, sc_maxskew=sc_maxskew,
        sc_hard=sc_hard, sc_valid=sc_valid,
        sc_min_domains=sc_min_domains, sc_honor_affinity=sc_honor_affinity,
        sc_honor_taints=sc_honor_taints,
        vol_terms=TermSet(**vol_a), vol_group=vol_group,
        vol_group_valid=vol_group_valid,
        rwo_pv=rwo_pv, rwo_valid=rwo_valid, attach_req=attach_req,
    )


def assert_same_batch(got: PodBatch, want: PodBatch):
    """Leaf for leaf: value (NaN-aware), dtype, shape."""
    g, gdef = jax.tree_util.tree_flatten_with_path(got)
    x, xdef = jax.tree_util.tree_flatten_with_path(want)
    assert gdef == xdef and len(g) == 89
    for (path, a), (_p, b) in zip(g, x):
        name = jax.tree_util.keystr(path)
        assert isinstance(a, np.ndarray), name
        assert a.dtype == b.dtype, (name, a.dtype, b.dtype)
        assert a.shape == b.shape, (name, a.shape, b.shape)
        assert np.array_equal(a, b, equal_nan=a.dtype.kind == "f"), name


# ---- subjects -------------------------------------------------------------

def _container(**extra) -> dict:
    return {"name": "c0", "resources": {"requests": {
        "cpu": "250m", "memory": "256Mi"}}, **extra}


def _pod(name: str, labels: dict, containers: list, **spec) -> dict:
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default",
                         "labels": labels},
            "spec": {"containers": containers, **spec},
            "status": {"phase": "Pending"}}


def plain_pod(name: str = "plain") -> dict:
    """No constraint group at all: not even an image."""
    return _pod(name, {"app": "plain"}, [_container()])


def all_groups_pod(name: str = "all-groups") -> dict:
    """Populates every one of the twelve groups of ``_ROW_GROUPS``."""
    term = {"topologyKey": ZONE,
            "labelSelector": {"matchLabels": {"app": "db"}},
            "namespaces": ["team-a", "team-b"]}
    return _pod(
        name, {"app": "web", "tier": "front"},
        [_container(image="registry.local/web:1.2",
                    ports=[{"containerPort": 8080, "hostPort": 8080,
                            "protocol": "TCP"}])],
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
                    {"weight": 40, "podAffinityTerm": term}]},
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    dict(term, topologyKey=HOSTNAME)],
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 20, "podAffinityTerm": dict(
                        term, topologyKey=HOSTNAME)}]}},
        topologySpreadConstraints=[
            {"maxSkew": 2, "topologyKey": ZONE, "minDomains": 3,
             "whenUnsatisfiable": "DoNotSchedule",
             "labelSelector": {"matchLabels": {"app": "web"}}}],
        volumes=[{"name": "data",
                  "persistentVolumeClaim": {"claimName": "data"}}])


def wide_pod(name: str = "wide") -> dict:
    """Wider than any subject in the groups it carries, so that encoding it
    promotes the row signature: three tolerations and selector pairs, two
    required terms of three expressions with three values, two spreads, two
    anti-affinity terms over three namespaces, two ports and images."""
    exprs = [{"key": k, "operator": "In", "values": ["a", "b", "c"]}
             for k in ("rack", "row", "hall")]
    anti = [{"topologyKey": HOSTNAME, "namespaces": ["n1", "n2", "n3"],
             "labelSelector": {"matchExpressions": exprs}}] * 2
    return _pod(
        name, {"app": "wide"},
        [_container(image="registry.local/wide:1", ports=[
            {"containerPort": p, "hostPort": p} for p in (9000, 9001)]),
         dict(_container(image="registry.local/side:1"), name="c1")],
        tolerations=[{"key": f"k{i}", "operator": "Exists"}
                     for i in range(3)],
        nodeSelector={"disk": "ssd", "rack": "a", "row": "b"},
        affinity={
            "nodeAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": {
                    "nodeSelectorTerms": [{"matchExpressions": exprs}] * 2}},
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": anti}},
        topologySpreadConstraints=[
            {"maxSkew": 1, "topologyKey": k,
             "whenUnsatisfiable": "ScheduleAnyway",
             "labelSelector": {"matchLabels": {"app": "wide"}}}
            for k in (ZONE, HOSTNAME)])


def _catalog() -> VolumeCatalog:
    """PVC ``data`` bound to a ReadWriteOnce PV pinned to one zone: volume
    terms, one group and one node-exclusive PV for the all-groups pod."""
    return VolumeCatalog.from_lists(
        pvcs=[{"metadata": {"name": "data", "namespace": "default"},
               "spec": {"volumeName": "pv-a",
                        "accessModes": ["ReadWriteOnce"]}}],
        pvs=[{"metadata": {"name": "pv-a"},
              "spec": {"accessModes": ["ReadWriteOnce"],
                       "nodeAffinity": {"required": {"nodeSelectorTerms": [
                           {"matchExpressions": [
                               {"key": ZONE, "operator": "In",
                                "values": ["zone-0"]}]}]}}}}])


def _subject(name: str):
    """-> (Node objects, pod dicts) of one subject, small enough for a
    sandbox CPU and large enough to mix carriers with plain pods."""
    if name == "hand_built":
        from yardstick.generators._objects import uniform_nodes
        pods = [all_groups_pod(), plain_pod("plain-0"),
                all_groups_pod("all-groups-1"), plain_pod("plain-1"),
                plain_pod("plain-2")]
        return [Node.from_dict(n) for n in uniform_nodes(8)], pods
    gen = importlib.import_module(f"yardstick.generators.{name}")
    nodes, pods = gen.generate(26, 16, 48)
    return [Node.from_dict(n) for n in nodes], pods


def _encoder(nodes, warm):
    enc = SnapshotEncoder()
    enc.set_volumes(_catalog())
    _ct, meta = enc.encode_cluster(nodes, [], warm)
    return enc, meta


# ---- the equivalence, one case a (subject, route) pair --------------------

@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("subject", GENERATORS + ("hand_built",))
def test_sparse_batch_equals_the_dense_reference(subject, route):
    nodes, dicts = _subject(subject)
    pods = [Pod.from_dict(d) for d in dicts]
    packable = sum(1 for p in pods if not p.spec.volumes)
    enc, meta = _encoder(nodes, pods)
    P = 64

    def encode():
        was = enc.pod_rows_stacked, enc.pod_rows_filled
        got = enc.encode_pods(pods, meta, min_p=P)
        assert_same_batch(got, dense_batch(enc, pods, meta, P))
        return (enc.pod_rows_stacked - was[0], enc.pod_rows_filled - was[1])

    if route == "event":
        # the signature as a warm-up drain leaves it, on other Pod objects
        # than the watch will deliver; then every pod at event time
        enc.encode_pods([Pod.from_dict(d) for d in dicts], meta, min_p=P)
        assert sum(enc.precompile_pod(p) for p in pods) == packable
        assert encode() == (packable, len(pods) - packable)
    elif route == "cold":
        assert encode() == (0, len(pods))
    elif route == "repop":
        enc.encode_pods(pods, meta, min_p=P)
        assert encode() == (packable, len(pods) - packable)
    else:
        # packs precompiled at one signature, a wider pod promotes it
        # mid-stream: every cached pack is stale and is rebuilt at the new
        # widths, and the pop after that stacks the rebuilt ones
        enc.encode_pods([Pod.from_dict(d) for d in dicts], meta, min_p=P)
        for p in pods:
            enc.precompile_pod(p)
        sig = enc._row_sig
        enc.encode_pods([Pod.from_dict(wide_pod())], meta, min_p=P)
        assert enc._row_sig != sig
        assert encode() == (0, len(pods))
        assert encode() == (packable, len(pods) - packable)


def test_a_pack_holds_only_the_groups_its_pod_populates():
    nodes, _ = _subject("hand_built")
    pods = [Pod.from_dict(plain_pod()), Pod.from_dict(all_groups_pod())]
    enc, meta = _encoder(nodes, pods)
    enc.encode_pods(pods, meta)
    resources, K, NSB, w = enc._row_env
    always = {"priority", "ns", "attach_req", "requests", "labels"}
    every = {f for fields in _ROW_GROUPS.values() for f in fields}
    assert len(_ROW_GROUPS) == 12 and len(every) == 78
    plain = enc._build_rows(enc._compile_pod(pods[0]), resources, K, NSB, w)
    assert set(plain) == always
    full = enc._build_rows(enc._compile_pod(pods[1]), resources, K, NSB, w)
    assert set(full) == always | every
    # a claim with no PV behind it: a volume group, no volume term
    lost = all_groups_pod("lost-claim")
    lost["spec"]["volumes"][0]["persistentVolumeClaim"]["claimName"] = "gone"
    c = enc._compile_pod(Pod.from_dict(lost))
    assert c["vol_groups"] == 1 and not c["vol_terms"]
    rows = enc._build_rows(c, resources, K, NSB, w)
    assert "vol_group_valid" in rows and "vol_key" not in rows


# ---- the counter ----------------------------------------------------------

def _row_group_series() -> dict:
    out = {}
    for line in REGISTRY.expose_text().splitlines():
        if line.startswith("scheduler_encode_row_groups_total{"):
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


def test_row_group_counters_count_built_and_default_once_a_pack():
    nodes, _ = _subject("hand_built")
    plain, full = Pod.from_dict(plain_pod()), Pod.from_dict(all_groups_pod())
    enc, meta = _encoder(nodes, [plain, full])
    assert (enc.row_groups_built, enc.row_groups_default) == (0, 0)
    enc.encode_pods([plain], meta)
    assert (enc.row_groups_built, enc.row_groups_default) == (0, 12)
    # a re-pop stacks the cached pack: nothing is built, nothing counted
    enc.encode_pods([plain], meta)
    assert (enc.row_groups_built, enc.row_groups_default) == (0, 12)
    enc.encode_pods([full], meta)
    assert (enc.row_groups_built, enc.row_groups_default) == (12, 12)
    # the exposition sums every live encoder's pair: this one's is in it
    after = _row_group_series()
    built = 'scheduler_encode_row_groups_total{kind="built"}'
    default = 'scheduler_encode_row_groups_total{kind="default"}'
    assert set(after) == {built, default}
    assert after[built] >= 12 and after[default] >= 12
