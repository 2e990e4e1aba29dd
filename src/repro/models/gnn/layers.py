"""The paper's GNN operator set (Appendix A.2) as pure functions.

Each layer consumes the padded neighbor-table representation:

  ``h``      — (N, d) node embeddings for *all* nodes of the (sub)graph,
  ``table``  — (N, fanout) int32 neighbor ids (padded),
  ``mask``   — (N, fanout) float {0,1} validity,

so the mean aggregation of Eq. 1/3/4 is a dense gather + masked mean, which
XLA lowers to efficient dynamic-gathers on TPU.  Every aggregate op also
accepts prebuilt :class:`repro.models.gnn.agg.AggOperands` (``agg=``): the
``csr`` layout replaces the ``N·fanout·d`` dense gather with an ``E·d``
edge-centric segment-sum, ``bcsr_kernel`` routes the mean/sym
aggregations through the Pallas BCSR SpMM — the full-neighbor paths of the
server-correction step and exact serving — and ``bucketed`` splits the
full-neighbor table by degree (GAT's attention reads the buckets of both).
``agg=None`` (the default) is the unchanged padded path.  ``rows`` (the
output rows' own slots, :func:`repro.models.gnn.agg.row_slots`) computes a
layer for those rows alone, gathering their slots from the whole ``h``.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.gnn.agg import (
    AggOperands, bcsr_mean_aggregate, bcsr_sym_aggregate,
    bucketed_gat_aggregate, bucketed_mean_aggregate, bucketed_sym_aggregate,
    DegreeBuckets, csr_gat_aggregate, csr_mean_aggregate, csr_sym_aggregate,
    gat_aggregate, row_ids,
)


def mean_aggregate(h: jnp.ndarray, table: jnp.ndarray, mask: jnp.ndarray,
                   agg: Optional[AggOperands] = None,
                   rows: Optional[DegreeBuckets] = None) -> jnp.ndarray:
    """(1/|Ñ(v)|) Σ_{j∈Ñ(v)} h_j — the paper's mean aggregation."""
    with jax.named_scope("aggregate"):
        if rows is not None:
            return bucketed_mean_aggregate(h, rows)
        if agg is not None:
            if agg.layout == "csr":
                return csr_mean_aggregate(h, agg.edges)
            if agg.layout == "bcsr_kernel":
                return bcsr_mean_aggregate(h, agg.bcsr)
            if agg.layout == "bucketed":
                return bucketed_mean_aggregate(h, agg.buckets)
        gathered = h[table]                       # (N, fanout, d)
        s = jnp.einsum("nfd,nf->nd", gathered, mask)
        denom = jnp.clip(mask.sum(-1, keepdims=True), 1.0, None)
        return s / denom


def sym_aggregate(h: jnp.ndarray, table: jnp.ndarray, mask: jnp.ndarray,
                  normalizers: jnp.ndarray,
                  agg: Optional[AggOperands] = None) -> jnp.ndarray:
    """Σ_j h_j / sqrt(deg_i · deg_j) — GCN symmetric-Laplacian aggregation."""
    with jax.named_scope("aggregate"):
        if agg is not None:
            if agg.layout == "csr":
                return csr_sym_aggregate(h, agg.edges, normalizers)
            if agg.layout == "bcsr_kernel":
                return bcsr_sym_aggregate(h, agg.bcsr, normalizers)
            if agg.layout == "bucketed":
                return bucketed_sym_aggregate(h, agg.buckets, normalizers)
        gathered = h[table]                       # (N, fanout, d)
        coef = mask * normalizers[table] * normalizers[:, None]
        return jnp.einsum("nfd,nf->nd", gathered, coef)


def gcn_layer(params: Dict, h: jnp.ndarray, table: jnp.ndarray,
              mask: jnp.ndarray, activation=jax.nn.relu,
              agg: Optional[AggOperands] = None,
              rows: Optional[DegreeBuckets] = None) -> jnp.ndarray:
    """Eq. 1: σ(mean_{j∈N(v)}(h_j) W)."""
    a = mean_aggregate(h, table, mask, agg=agg, rows=rows)
    out = a @ params["w"]
    if "b" in params:
        out = out + params["b"]
    return activation(out) if activation is not None else out


def sage_layer(params: Dict, h: jnp.ndarray, table: jnp.ndarray,
               mask: jnp.ndarray, activation=jax.nn.relu,
               agg: Optional[AggOperands] = None,
               rows: Optional[DegreeBuckets] = None) -> jnp.ndarray:
    """Eq. 7: σ(h W1 + mean_nbr(h) W2)."""
    a = mean_aggregate(h, table, mask, agg=agg, rows=rows)
    if rows is not None:
        h = h[row_ids(rows)]
    out = h @ params["w_self"] + a @ params["w_nbr"]
    if "b" in params:
        out = out + params["b"]
    return activation(out) if activation is not None else out


def gat_layer(params: Dict, h: jnp.ndarray, table: jnp.ndarray,
              mask: jnp.ndarray, heads: int = 1, self_loop: bool = False,
              negative_slope: float = 0.2, fused: bool = False,
              agg: Optional[AggOperands] = None,
              rows: Optional[DegreeBuckets] = None) -> jnp.ndarray:
    """Eq. 10/11 with ``heads`` heads: ``(N, H·F)``, head k in columns
    ``k·F .. (k+1)·F``, before any bias or activation.

    ``z = h W`` as ``(N, H, F)``; ``s_src = z·a_src`` scores a node as a
    neighbour, ``s_dst = z·a_dst`` as the row, each ``(N, H)`` in XLA; row
    i attends over its slots j (and itself, with ``self_loop``) by the
    masked softmax of ``LeakyReLU(s_dst[i] + s_src[j])`` and sums ``z[j]``
    per head.  With a residual projection ``params["r"]``, ``h R`` is
    added.  The slots are the sampled ``table``/``mask``, or the degree
    buckets that ``agg`` carries (the ``bucketed`` and ``bcsr_kernel``
    layouts; the full-neighbor forwards), or the edge list of the ``csr``
    layout.  ``fused=True`` runs the forward's neighbour gather and sum in
    the Pallas kernel (``repro.kernels.edge_softmax``), which holds the
    gathered rows in VMEM only.  With ``rows`` the attention and ``h R``
    run for those rows alone; ``z`` and the scores stay over every node,
    which the rows' neighbours need.
    """
    n = h.shape[0]
    z = h @ params["w"]                                   # (N, H·F)
    z3 = z.reshape(n, heads, -1)
    s_src = jnp.sum(z3 * params["a_src"].reshape(heads, -1), axis=-1)
    s_dst = jnp.sum(z3 * params["a_dst"].reshape(heads, -1), axis=-1)
    kw = dict(negative_slope=negative_slope, self_loop=self_loop)
    if rows is not None:
        out = bucketed_gat_aggregate(z, s_src, s_dst, rows, fused=fused,
                                     **kw)
        h = h[row_ids(rows)]
    elif agg is not None and agg.layout == "csr":
        out = csr_gat_aggregate(z, s_src, s_dst, agg.edges, **kw)
    elif agg is not None and agg.buckets is not None:
        out = bucketed_gat_aggregate(z, s_src, s_dst, agg.buckets,
                                     fused=fused, **kw)
    else:
        out = gat_aggregate(z, s_src, s_dst, table, mask, fused=fused, **kw)
    if "r" in params:
        out = out + h @ params["r"]
    return out


def linear_layer(params: Dict, h: jnp.ndarray, *_, activation=None,
                 **__) -> jnp.ndarray:
    """Eq. 8: graph-agnostic h W (the paper's 'L' op / the MLP ablation)."""
    out = h @ params["w"]
    if "b" in params:
        out = out + params["b"]
    return activation(out) if activation is not None else out


def batch_norm(params: Dict, h: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Eq. 9 over the node axis, using batch statistics (training mode).

    Statistics are computed over whatever node set the machine can see —
    under partitioning each machine normalizes with *local* statistics, one
    more (realistic) source of local-global discrepancy.
    """
    mean = h.mean(axis=0, keepdims=True)
    var = h.var(axis=0, keepdims=True)
    hhat = (h - mean) / jnp.sqrt(var + eps)
    return hhat * params["gamma"] + params["beta"]


def appnp_propagate(h0: jnp.ndarray, table: jnp.ndarray, mask: jnp.ndarray,
                    num_steps: int, beta: float,
                    agg: Optional[AggOperands] = None) -> jnp.ndarray:
    """Eq. 12: h ← β h0 + (1−β) Â h, iterated ``num_steps`` times."""
    def body(h, _):
        h = beta * h0 + (1.0 - beta) * mean_aggregate(h, table, mask, agg=agg)
        return h, None
    out, _ = jax.lax.scan(body, h0, None, length=num_steps)
    return out
