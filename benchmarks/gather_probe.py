"""Time one 256-wide full-neighbor mean aggregation at ogbn-arxiv size
(the ``arxiv-gbgbg`` benchmark configuration's SBM: 169,343 nodes, average
degree 6.9 before symmetrizing), forward and gradient with respect to ``h``:

* ``pad0``: the single ``(N, max_deg)`` table as ``build_neighbor_table``
  builds it, pad slots at node 0;
* ``padself``: the same table with pad slots at the row's own node;
* ``buckets``: the degree buckets through ``mean_aggregate(agg=...)``;
* ``buckets_autodiff`` / ``buckets_gather_vjp``: the same buckets with the
  permutation back to node order written here, transposed by autodiff
  (a scatter-add) or by a ``custom_vjp`` that gathers by the inverse
  permutation.

Run on the accelerator from the repo root::

    PYTHONPATH=src python -m benchmarks.gather_probe [--reps 20]

Prints one line per case and direction with the median and the minimum
over ``--reps`` timed calls after one warm-up call.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph import sbm_graph
from repro.graph.csr import build_neighbor_table
from repro.models.gnn import layers as L
from repro.models.gnn.agg import bucketed_operands


@jax.custom_vjp
def _permute_gather_vjp(x, perm, inv):
    return x[perm]


def _pgv_fwd(x, perm, inv):
    return x[perm], (perm, inv)


def _pgv_bwd(res, g):
    perm, inv = res
    ft0 = np.zeros(np.shape(perm), jax.dtypes.float0)
    return g[inv], ft0, ft0


_permute_gather_vjp.defvjp(_pgv_fwd, _pgv_bwd)


def _permute_autodiff(x, perm, inv):
    return x[perm]


def _bucket_mean(h, buckets, permute):
    parts = []
    for tab, mask in zip(buckets.tables, buckets.masks):
        s = jnp.einsum("nfd,nf->nd", h[tab], mask)
        parts.append(s / jnp.clip(mask.sum(-1, keepdims=True), 1.0, None))
    return permute(jnp.concatenate(parts), buckets.slot_of, buckets.order)


def _time(f, args, reps):
    f(*args).block_until_ready()
    ts = []
    for _ in range(reps):
        s = time.perf_counter()
        f(*args).block_until_ready()
        ts.append(time.perf_counter() - s)
    return 1e3 * float(np.median(ts)), 1e3 * min(ts)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--nodes", type=int, default=169_343)
    args = ap.parse_args()
    jax.config.update("jax_default_matmul_precision", "high")

    d = sbm_graph(num_nodes=args.nodes, num_classes=40, feature_dim=128,
                  avg_degree=6.9, homophily=0.9, feature_snr=0.3, seed=0)
    t0, m = build_neighbor_table(d.graph)
    n = t0.shape[0]
    tself = np.where(m > 0, t0, np.arange(n)[:, None]).astype(np.int32)
    agg = bucketed_operands(d.graph)
    h = jax.random.normal(jax.random.PRNGKey(0), (n, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (n, 256), jnp.float32)
    m_j = jnp.asarray(m)

    # operands are jit arguments, not constants folded into the program
    single = lambda h, tm: L.mean_aggregate(h, tm[0], tm[1])  # noqa: E731
    cases = {
        "pad0": (single, (jnp.asarray(t0), m_j)),
        "padself": (single, (jnp.asarray(tself), m_j)),
        "buckets": (lambda h, a: L.mean_aggregate(h, None, None, agg=a),
                    agg),
        "buckets_autodiff": (lambda h, a: _bucket_mean(
            h, a.buckets, _permute_autodiff), agg),
        "buckets_gather_vjp": (lambda h, a: _bucket_mean(
            h, a.buckets, _permute_gather_vjp), agg),
    }
    print(f"nodes={n} max_deg={t0.shape[1]} single_slots={t0.size} "
          f"bucket_slots={sum(t.size for t in agg.buckets.tables)} "
          f"buckets={len(agg.buckets.tables)} "
          f"device={jax.devices()[0].device_kind}", flush=True)
    for name, (fn, ops) in cases.items():
        fwd = jax.jit(fn)
        bwd = jax.jit(jax.grad(
            lambda h, ops, w, fn=fn: (fn(h, ops) * w).sum()))
        for direction, f, fargs in (("fwd", fwd, (h, ops)),
                                    ("bwd", bwd, (h, ops, w))):
            med, lo = _time(f, fargs, args.reps)
            print(f"{name} {direction} median_ms={med:.3f} min_ms={lo:.3f}",
                  flush=True)


if __name__ == "__main__":
    main()
