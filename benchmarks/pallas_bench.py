"""Domain-count hot-op benchmark — the measurement that retired the Pallas
kernel (VERDICT r3 ask #6: prove or delete).

History: ``ops/pallas/domain_count.py`` fused the [E,P,T] selector match
with the per-node count so the match tensor never left VMEM. Measured on
the real v5e chip (round 4, forced per-iteration materialization so async
dispatch could not flatter either side, 16384 epods x 1024 pods x 4 terms x
5120 nodes):

    XLA match+einsum : ~122 ms/eval
    Pallas kernel    : ~14,712 ms/eval  (120x SLOWER)

Root causes: at MXU-friendly tiles (128/128/256) Mosaic's register
allocator spilled ~74 MiB of VMEM stack (fixable via
CompilerParams.vmem_limit_bytes), but even then the 82k-step grid of tiny
HIGHEST-precision dots starved the MXU while XLA fuses the same chain into
a handful of large contractions. The kernel was deleted; this benchmark
keeps the LIVE number for the XLA path that won.

Run on the real chip:
    python benchmarks/pallas_bench.py [E] [P] [T]

Prints one JSON line with the live xla_ms and the recorded comparison.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# the round-4 measurement that decided deletion (see module docstring)
RETIRED_KERNEL = {
    "status": "deleted_round4_lost_to_xla",
    "measured_on": "v5e, round 4, forced materialization, 16384x1024x4x5120",
    "pallas_ms": 14712.0,
    "xla_ms": 122.0,
    "pallas_vs_xla": 0.0083,
}


def run_domain_count(E: int = 16384, P: int = 1024, T: int = 4) -> dict:
    N, K, X, V = 5120, 64, 4, 2
    rng = np.random.default_rng(0)

    epod_labels = jnp.asarray(rng.integers(-1, 32, (E, K)), jnp.int32)
    epod_node = jnp.asarray(rng.integers(0, N, E), jnp.int32)
    epod_ns = jnp.asarray(rng.integers(0, 4, E), jnp.int32)
    epod_valid = jnp.ones(E, bool)
    sel_key = jnp.asarray(rng.integers(0, K, (P, T, X)), jnp.int32)
    sel_op = jnp.asarray(rng.integers(0, 4, (P, T, X)), jnp.int32)
    sel_ev = jnp.ones((P, T, X), bool)
    sel_vals = jnp.asarray(rng.integers(-1, 32, (P, T, X, V)), jnp.int32)
    sel_valid = jnp.ones((P, T), bool)
    pod_ns = jnp.asarray(rng.integers(0, 4, P), jnp.int32)

    from kubernetes_tpu.encode.snapshot import SelectorSet
    from kubernetes_tpu.ops.exprs import eval_selector_set

    sel = SelectorSet(key=sel_key, op=sel_op, vals=sel_vals,
                      expr_valid=sel_ev, valid=sel_valid)

    @jax.jit
    def xla_path(labels, node, ns, valid, pns, salt):
        # salt defeats any same-args result reuse in remote runtimes; the
        # scalar sum forces full materialization before the clock stops
        m = eval_selector_set(sel, labels + salt - salt)     # [E,P,T]
        ns_ok = ns[:, None] == pns[None, :]
        m = (m & ns_ok[:, :, None] & valid[:, None, None]).astype(jnp.float32)
        onehot = (node[:, None] == jnp.arange(N)[None, :]).astype(jnp.float32)
        return jnp.sum(jnp.einsum("ept,en->ptn", m, onehot))

    args = (epod_labels, epod_node, epod_ns, epod_valid, pod_ns)
    float(xla_path(*args, jnp.int32(0)))  # compile
    iters = 10
    t0 = time.perf_counter()
    for i in range(iters):
        float(xla_path(*args, jnp.int32(i)))
    t_xla = (time.perf_counter() - t0) / iters
    return {
        "metric": "domain_count_hot_op",
        "backend": jax.default_backend(),
        "shape": {"E": E, "P": P, "T": T, "N": N},
        "xla_ms": round(t_xla * 1e3, 3),
        "retired_pallas_kernel": RETIRED_KERNEL,
    }


def main():
    print(json.dumps(run_domain_count(
        E=int(sys.argv[1]) if len(sys.argv) > 1 else 16384,
        P=int(sys.argv[2]) if len(sys.argv) > 2 else 1024,
        T=int(sys.argv[3]) if len(sys.argv) > 3 else 4)))


if __name__ == "__main__":
    from kubernetes_tpu.parallel.aot import place_compile_cache
    place_compile_cache()
    main()
