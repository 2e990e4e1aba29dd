"""Aggregation-layout engine: one pluggable aggregate op, three layouts.

Every GNN aggregation in the repo lowers to the padded neighbor-table form
``h[table] → (N, fanout, d)``, whose cost is ``N·fanout·d`` regardless of
how much of the table is padding.  That is the right layout for the sampled
local rounds (narrow tables, mostly full), but the server-correction phase
and ``fanout=None`` exact serving run *full-neighbor* forwards where
``fanout = max_degree`` and power-law degree skew makes the table mostly
zeros.  This module makes the layout a selectable property instead of a
baked-in lowering:

``layout="padded"``
    The existing dense gather + masked reduction.  Bit-identical default.

``layout="csr"``
    Pure-XLA edge-centric path: a ``segment_sum`` over the graph's CSR edge
    list costs ``E·d`` with zero padding waste.  The mean/sym reductions go
    through :func:`edge_weighted_sum`, a ``custom_vjp`` whose backward is
    the transposed scatter-add over edges — never a dense-table gradient.

``layout="bcsr_kernel"``
    Full-graph aggregation through the Pallas BCSR SpMM
    (:func:`repro.kernels.spmm.spmm_bcsr`) with an unnormalized-adjacency
    operand (symmetric, so the ``custom_vjp`` backward reuses the same
    tiles); GAT's attention runs over the degree buckets (below), which
    these operands carry too; interpreted on the CPU backend, compiled on
    a TPU (:func:`repro.kernels.ops.pallas_interpret`).

``layout="auto"``
    :func:`choose_layout` picks per (graph, table width, sampling) via a
    simple cost model: padded work is ``N·width``, edge-centric work is
    ``E``; once the padded table is mostly padding (the full-neighbor
    correction / serving regimes) the csr path wins.  Sampled (narrowed)
    tables always resolve to padded — the edge-centric operands encode the
    FULL edge set, which is different math from a subsampled table.

Degree buckets (:func:`degree_buckets`, layout ``"bucketed"``)
    The padded path itself, split by degree: nodes grouped by their degree
    rounded up (multiples of 8 to 32, then powers of two), one
    ``(N_b, w_b)`` table per bucket, and one permutation back to node
    order.  Each row sums the same neighbors in the same slot order as the
    single ``max_deg``-wide table; only all-padding slots are dropped.  Not
    a user option: every full-neighbor consumer takes it, GAT's attention
    (:func:`bucketed_gat_aggregate`) included.  A near-regular graph comes
    out as one bucket in node order, which is the single table and its
    program.

Operands are prebuilt host-side once per graph and cached on the graph
object (the ``_all_nodes_plan`` / ``RoundSampler.prewarm`` idiom), so no
layout pays a rebuild inside the round.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph, gather_neighbor_rows

#: Selectable aggregation layouts.
LAYOUTS = ("padded", "csr", "bcsr_kernel", "auto")

#: ``auto`` picks the edge-centric path once padded work ≥ threshold · edge
#: work.  2.0 keeps padded for near-dense tables where the gather's locality
#: beats the scatter.
AUTO_THRESHOLD = 2.0

# --------------------------------------------------------------------------
# Operand containers (pytrees: jit/vmap/scan-safe, layout string is static)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EdgeCSR:
    """Edge-list operands for the csr layout.

    ``seg[e]`` is the owning (destination) row of edge ``e``, ``nbr[e]``
    the neighbor gathered from.  Padding edges (stacked multi-graph form)
    carry ``seg = num_segments`` — out of range, dropped by jax's segment
    ops — with ``nbr = 0`` (clamped, harmless) and zero weights/mask.
    Arrays are ``(E,)`` for one graph or ``(P, E_max)`` stacked for the
    serving backends' vmap over machines.
    """

    seg: Any                  # int32 — owner row per edge
    nbr: Any                  # int32 — neighbor row per edge
    w_mean: Any               # f32 — 1/max(deg,1)[seg]; 0 on padding
    emask: Any                # f32 — 1 real edge, 0 padding
    num_segments: int         # static output row count


def _edgecsr_flatten(e):
    return (e.seg, e.nbr, e.w_mean, e.emask), e.num_segments


def _edgecsr_unflatten(aux, children):
    return EdgeCSR(*children, num_segments=aux)


jax.tree_util.register_pytree_node(EdgeCSR, _edgecsr_flatten,
                                   _edgecsr_unflatten)


@dataclasses.dataclass(frozen=True)
class BCSROps:
    """Device-resident BCSR tiles of the UNnormalized adjacency.

    Normalization is applied outside the kernel as row/column scalings
    (mean = ``diag(1/deg)·A``, sym = ``diag(nrm)·A·diag(nrm)``), so ONE
    tile inventory serves every aggregate op and — A being symmetric — the
    backward pass reuses the same operands as the forward.
    """

    cols: Any                 # (n_rb, max_t) int32
    vals: Any                 # (n_rb, max_t, BM, BN) f32
    inv_deg: Any              # (N,) f32 — 1/max(deg,1)
    n_pad: int                # static padded row count


def _bcsr_flatten(b):
    return (b.cols, b.vals, b.inv_deg), b.n_pad


def _bcsr_unflatten(aux, children):
    return BCSROps(*children, n_pad=aux)


jax.tree_util.register_pytree_node(BCSROps, _bcsr_flatten, _bcsr_unflatten)


@dataclasses.dataclass(frozen=True)
class DegreeBuckets:
    """The full-neighbor table split into degree buckets.

    Bucket ``b`` holds the rows ``order[o_b : o_b + N_b]`` (``o_b`` the
    sum of the earlier buckets' row counts); its ``(N_b, w_b)`` table keeps
    each row's slots in the single table's order, and pad slots point at
    the row's own node with mask 0.  ``slot_of[v]`` is node ``v``'s row in
    the concatenated bucket outputs, so ``concat[slot_of]`` is node order
    (and ``order`` the inverse permutation).  A width-0 bucket holds the
    zero-degree nodes.
    """

    tables: Tuple[Any, ...]   # per bucket (N_b, w_b) int32
    masks: Tuple[Any, ...]    # per bucket (N_b, w_b) f32
    order: Any                # (N,) int32 — node at each concatenated row
    slot_of: Any              # (N,) int32 — concatenated row of each node


def _buckets_flatten(b):
    return (b.tables, b.masks, b.order, b.slot_of), None


def _buckets_unflatten(aux, children):
    return DegreeBuckets(*children)


jax.tree_util.register_pytree_node(DegreeBuckets, _buckets_flatten,
                                   _buckets_unflatten)


@dataclasses.dataclass(frozen=True)
class AggOperands:
    """The resolved layout + its prebuilt operands, threaded through
    ``GNNModel.apply`` down to the aggregate ops.  ``None`` anywhere in the
    stack means the padded path (bit-identical to pre-layout code)."""

    layout: str               # "csr" | "bcsr_kernel" | "bucketed" (static)
    edges: Optional[EdgeCSR] = None
    bcsr: Optional[BCSROps] = None
    buckets: Optional[DegreeBuckets] = None


def _agg_flatten(a):
    return (a.edges, a.bcsr, a.buckets), a.layout


def _agg_unflatten(aux, children):
    return AggOperands(aux, *children)


jax.tree_util.register_pytree_node(AggOperands, _agg_flatten, _agg_unflatten)


# --------------------------------------------------------------------------
# Host-side builders, cached per graph object (prewarm idiom)
# --------------------------------------------------------------------------
def _graph_cache(graph: CSRGraph) -> dict:
    cache = graph.__dict__.get("_agg_operand_cache")
    if cache is None:
        cache = {}
        object.__setattr__(graph, "_agg_operand_cache", cache)
    return cache


def edge_operands(graph: CSRGraph,
                  num_segments: Optional[int] = None) -> EdgeCSR:
    """One graph's :class:`EdgeCSR`, built once and cached on the graph."""
    ns = graph.num_nodes if num_segments is None else int(num_segments)
    cache = _graph_cache(graph)
    key = ("edges", ns)
    ops = cache.get(key)
    if ops is not None:
        return ops
    src, dst = graph.to_edges()
    deg = np.maximum(graph.degrees(), 1).astype(np.float32)
    e = src.shape[0]
    ops = EdgeCSR(seg=jnp.asarray(src, jnp.int32),
                  nbr=jnp.asarray(dst, jnp.int32),
                  w_mean=jnp.asarray((1.0 / deg)[src], jnp.float32),
                  emask=jnp.ones((e,), jnp.float32),
                  num_segments=ns)
    cache[key] = ops
    return ops


def stacked_edge_operands(graphs: Sequence[CSRGraph],
                          num_segments: int) -> EdgeCSR:
    """Stacked ``(P, E_max)`` edge operands for a vmapped forward over P
    partition-extended graphs (the serving backends).  Machines with fewer
    edges are padded with dropped edges (``seg = num_segments``)."""
    ns = int(num_segments)
    e_max = max(max(g.num_edges for g in graphs), 1)
    P = len(graphs)
    seg = np.full((P, e_max), ns, np.int32)
    nbr = np.zeros((P, e_max), np.int32)
    w = np.zeros((P, e_max), np.float32)
    em = np.zeros((P, e_max), np.float32)
    for p, g in enumerate(graphs):
        src, dst = g.to_edges()
        deg = np.maximum(g.degrees(), 1).astype(np.float32)
        e = src.shape[0]
        seg[p, :e] = src
        nbr[p, :e] = dst
        w[p, :e] = (1.0 / deg)[src]
        em[p, :e] = 1.0
    return EdgeCSR(seg=jnp.asarray(seg), nbr=jnp.asarray(nbr),
                   w_mean=jnp.asarray(w), emask=jnp.asarray(em),
                   num_segments=ns)


def bcsr_operands(graph: CSRGraph, block_m: int = 8,
                  block_n: int = 128) -> BCSROps:
    """The graph's unnormalized BCSR tiles + degree scaling, cached."""
    from repro.kernels.ops import bcsr_device_operands
    cols, vals, n_pad = bcsr_device_operands(graph, block_m, block_n, "none")
    cache = _graph_cache(graph)
    key = ("bcsr", block_m, block_n)
    ops = cache.get(key)
    if ops is None:
        deg = np.maximum(graph.degrees(), 1).astype(np.float32)
        ops = BCSROps(cols=cols, vals=vals,
                      inv_deg=jnp.asarray(1.0 / deg), n_pad=n_pad)
        cache[key] = ops
    return ops


def build_agg_operands(graph: CSRGraph, layout: str,
                       num_segments: Optional[int] = None
                       ) -> Optional[AggOperands]:
    """Resolve a concrete (non-auto) layout into its prebuilt operands.

    ``"padded"`` → ``None`` (the existing dense path, untouched).
    """
    if layout in (None, "padded"):
        return None
    if layout == "csr":
        return AggOperands("csr", edges=edge_operands(graph, num_segments))
    if layout == "bcsr_kernel":
        return AggOperands("bcsr_kernel",
                           edges=edge_operands(graph, num_segments),
                           bcsr=bcsr_operands(graph),
                           buckets=degree_buckets(graph))
    raise ValueError(f"unknown aggregation layout {layout!r}; "
                     f"choose one of {LAYOUTS}")


def bucket_widths(deg: np.ndarray, max_deg: int) -> np.ndarray:
    """Each node's bucket width: its degree rounded up to a multiple of 8
    up to 32, then up to a power of two (a logarithmic number of buckets on
    a heavy-tailed graph), never past ``max_deg``; 0 for zero-degree
    nodes."""
    deg = np.asarray(deg, np.int64)
    pow2 = 2 ** np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64)
    return np.minimum(np.where(deg <= 32, -(-deg // 8) * 8, pow2), max_deg)


def degree_buckets(graph: CSRGraph,
                   nodes: Optional[np.ndarray] = None) -> DegreeBuckets:
    """The graph's full-neighbor table as :class:`DegreeBuckets`, built once
    and cached on the graph.  With ``nodes``, the rows of those nodes
    alone, in their order (repeats kept): ``order`` holds node ids and
    ``slot_of[i]`` the concatenated row of ``nodes[i]``, so the bucketed
    aggregations return ``(len(nodes), d)`` (:func:`row_slots`)."""
    cache = _graph_cache(graph)
    key = "buckets"
    if nodes is not None:
        nodes = np.asarray(nodes, np.int64)
        key = ("buckets", nodes.tobytes())
    if key not in cache:
        deg = graph.degrees()
        ids = np.arange(graph.num_nodes) if nodes is None else nodes
        n = ids.size
        width = bucket_widths(deg[ids], max(graph.max_degree(), 1))
        pos = np.argsort(width, kind="stable")
        order = ids[pos]
        widths, starts = np.unique(width[pos], return_index=True)
        tables, masks = [], []
        for w, rows in zip(widths, np.split(order, starts[1:])):
            if w == 0:
                tab = np.zeros((rows.size, 0), np.int32)
                msk = np.zeros((rows.size, 0), np.float32)
            else:
                tab, msk = gather_neighbor_rows(graph, rows, int(w))
                tab = np.where(msk > 0, tab, rows[:, None]).astype(np.int32)
            tables.append(jnp.asarray(tab))
            masks.append(jnp.asarray(msk))
        slot_of = np.empty(n, np.int32)
        slot_of[pos] = np.arange(n, dtype=np.int32)
        cache[key] = DegreeBuckets(
            tables=tuple(tables), masks=tuple(masks),
            order=jnp.asarray(order, jnp.int32),
            slot_of=jnp.asarray(slot_of))
    return cache[key]


def row_ids(rows) -> Any:
    """The node ids of output rows given as ids or as their
    :class:`DegreeBuckets`, in output order."""
    if not isinstance(rows, DegreeBuckets):
        return rows
    return rows.order if len(rows.tables) == 1 else rows.order[rows.slot_of]


def row_slots(rows, table, mask,
              agg: Optional[AggOperands] = None) -> Optional[DegreeBuckets]:
    """The neighbour slots of the output rows ``rows`` as
    :class:`DegreeBuckets` whose aggregations come out in ``rows``' order.

    ``rows`` are node ids ``(R,)``, or prebuilt buckets
    (:func:`degree_buckets` with ``nodes``), returned as they are.  For ids:
    on the padded path one bucket of ``table[rows]``/``mask[rows]``; with
    degree buckets in ``agg``, each row's slots gathered out of its bucket
    on the device into one bucket as wide as the widest, the slots past the
    row's bucket padding that points at the row itself, so the program
    keeps one shape for any rows.  ``None`` where ``agg`` carries no buckets
    (the ``csr`` layout): there the rows are selected after the full
    aggregation.
    """
    if isinstance(rows, DegreeBuckets):
        return rows
    r = rows.shape[0]
    slot_of = jnp.arange(r, dtype=jnp.int32)
    if agg is None:
        return DegreeBuckets((table[rows],), (mask[rows],), rows, slot_of)
    if agg.buckets is None:
        return None
    b = agg.buckets
    width = max(t.shape[1] for t in b.tables)
    pos = b.slot_of[rows]
    tab = jnp.broadcast_to(rows[:, None], (r, width)).astype(jnp.int32)
    msk = jnp.zeros((r, width), jnp.float32)
    start = 0
    for t, m in zip(b.tables, b.masks):
        n_b, w_b = t.shape
        if n_b and w_b:
            i = pos - start
            mine = ((i >= 0) & (i < n_b))[:, None]
            i = jnp.clip(i, 0, n_b - 1)
            tab = tab.at[:, :w_b].set(jnp.where(mine, t[i], tab[:, :w_b]))
            msk = msk.at[:, :w_b].set(jnp.where(mine, m[i], msk[:, :w_b]))
        start += n_b
    return DegreeBuckets((tab,), (msk,), rows, slot_of)


def bucketed_operands(graph: CSRGraph) -> AggOperands:
    """:func:`degree_buckets` as :class:`AggOperands` for the full-neighbor
    aggregations."""
    return AggOperands("bucketed", buckets=degree_buckets(graph))


def full_table_stats(graph: CSRGraph) -> dict:
    """Slots gathered per full-neighbor aggregation, the real edges among
    them, and the number of tables: the engagement counters of
    :func:`degree_buckets`."""
    buckets = degree_buckets(graph)
    return {"full_agg_slots": int(sum(t.size for t in buckets.tables)),
            "full_agg_edges": graph.num_edges,
            "full_agg_buckets": len(buckets.tables)}


def choose_layout(layout: str, *, num_nodes: int, num_edges: int,
                  width: int, full_width: int, sampled: bool = False,
                  threshold: float = AUTO_THRESHOLD,
                  padded_slots: Optional[int] = None) -> str:
    """Resolve ``"auto"`` via the padding-fraction cost model.

    Padded-table work scales with the slots it gathers, ``padded_slots``
    (``num_nodes·width`` for one table; fewer where the full table is split
    into degree buckets); edge-centric work with ``num_edges``.  Sampled or
    narrowed tables (``width < full_width``) are different math from the
    full edge set and always resolve to padded.  ``auto`` never picks ``bcsr_kernel`` — on this
    container the Pallas kernels run in interpret mode, so the kernel
    layout is an explicit opt-in for real hardware.
    """
    if layout not in LAYOUTS:
        raise ValueError(f"unknown aggregation layout {layout!r}; "
                         f"choose one of {LAYOUTS}")
    if layout != "auto":
        return layout
    if sampled or width < full_width:
        return "padded"
    padded_work = (num_nodes * max(int(width), 1) if padded_slots is None
                   else int(padded_slots))
    if padded_work >= threshold * max(int(num_edges), 1):
        return "csr"
    return "padded"


# --------------------------------------------------------------------------
# Edge-centric aggregate primitives (csr layout)
# --------------------------------------------------------------------------
# The custom_vjp primitives are MODULE-LEVEL functions taking every operand
# as an explicit argument (indices get float0 cotangents).  A closure-style
# custom_vjp capturing the operand arrays breaks when the aggregate runs
# inside a lax.scan body (APPNP's propagation loop, the engine's corr_scan):
# the captured arrays surface as invalid tracer constants in the scan
# lowering.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _edge_weighted_sum(num_segments, x, w, seg, nbr):
    return jax.ops.segment_sum(x[nbr] * w[:, None], seg,
                               num_segments=num_segments)


def _ews_fwd(num_segments, x, w, seg, nbr):
    return _edge_weighted_sum(num_segments, x, w, seg, nbr), (x, w, seg, nbr)


def _ews_bwd(num_segments, res, g):
    x, w, seg, nbr = res
    segc = jnp.minimum(seg, num_segments - 1)   # pad edges: zeroed below
    ge = g[segc]
    gx = jax.ops.segment_sum(ge * w[:, None], nbr,
                             num_segments=x.shape[0])
    gw = jnp.where(seg < num_segments, (ge * x[nbr]).sum(-1), 0.0)
    ft0 = np.zeros(np.shape(seg), jax.dtypes.float0)
    return gx, gw.astype(w.dtype), ft0, ft0


_edge_weighted_sum.defvjp(_ews_fwd, _ews_bwd)


def edge_weighted_sum(h: jnp.ndarray, seg, nbr, w, num_segments: int
                      ) -> jnp.ndarray:
    """``out[i] = Σ_{e: seg[e]=i} w[e]·h[nbr[e]]`` — E·d work, no padding.

    The ``custom_vjp`` pins the backward to the transposed scatter-add over
    edges (``h̄[j] = Σ_{e: nbr[e]=j} w[e]·ḡ[seg[e]]``) instead of whatever
    gradient a dense-table formulation would materialize.
    """
    return _edge_weighted_sum(int(num_segments), h, w.astype(h.dtype),
                              seg, nbr)


def csr_mean_aggregate(h: jnp.ndarray, edges: EdgeCSR) -> jnp.ndarray:
    """Edge-centric mean aggregation — the 1/deg normalization is folded
    into the per-edge weights (padded path divides by the mask sum, which
    at full width IS the degree)."""
    return edge_weighted_sum(h, edges.seg, edges.nbr, edges.w_mean,
                             edges.num_segments)


def csr_sym_aggregate(h: jnp.ndarray, edges: EdgeCSR,
                      normalizers: jnp.ndarray) -> jnp.ndarray:
    """Edge-centric ``Σ_j h_j · nrm_i · nrm_j`` (exact for any runtime
    normalizer vector, unlike a prebaked normalized operand)."""
    nrm = normalizers.astype(h.dtype)
    segc = jnp.minimum(edges.seg, edges.num_segments - 1)
    w = edges.emask.astype(h.dtype) * nrm[segc] * nrm[edges.nbr]
    return edge_weighted_sum(h, edges.seg, edges.nbr, w, edges.num_segments)


def csr_gat_aggregate(z: jnp.ndarray, s_src: jnp.ndarray,
                      s_dst: jnp.ndarray, edges: EdgeCSR, *,
                      negative_slope: float = 0.2,
                      self_loop: bool = False, **_) -> jnp.ndarray:
    """Edge-centric masked GAT softmax-aggregate, per head.

    ``z (N, H·F)``, scores ``s_src``/``s_dst (N, H)``: edge ``i ← j`` scores
    ``LeakyReLU(s_dst[i] + s_src[j])``; a ``segment_max``-stabilized
    softmax over each node's real edges (and itself, with ``self_loop``),
    then the weighted segment-sum — all E-sized.  Zero-degree rows without
    the self term emit zeros, matching the padded path's all-pad-row
    convention.  Differentiable in ``z`` and the scores through jax's
    segment ops (their transposes are already edge-centric gathers).
    """
    seg, nbr, emask, ns = edges.seg, edges.nbr, edges.emask, edges.num_segments
    if self_loop:
        own = jnp.arange(ns, dtype=seg.dtype)
        seg, nbr = jnp.concatenate([seg, own]), jnp.concatenate([nbr, own])
        emask = jnp.concatenate([emask, jnp.ones((ns,), emask.dtype)])
    heads = s_src.shape[1]
    segc = jnp.minimum(seg, ns - 1)
    e = jax.nn.leaky_relu(s_dst[segc] + s_src[nbr], negative_slope)
    valid = (emask > 0)[:, None]
    neg = jnp.asarray(-1e30, e.dtype)
    m = jax.ops.segment_max(jnp.where(valid, e, neg), seg, num_segments=ns)
    # softmax shift: constant per segment, gradient cancels — and clamping
    # keeps zero-degree rows (max = -inf) finite
    m = jax.lax.stop_gradient(jnp.maximum(m, neg))
    num = jnp.exp(e - m[segc]) * emask.astype(e.dtype)[:, None]
    den = jax.ops.segment_sum(num, seg, num_segments=ns)         # (N, H)
    zj = z[nbr].reshape(nbr.shape[0], heads, -1)
    out = jax.ops.segment_sum(num[..., None] * zj, seg, num_segments=ns)
    out = out / jnp.maximum(den, 1e-30)[..., None]
    return out.reshape(ns, -1)


def gat_aggregate(z: jnp.ndarray, s_src: jnp.ndarray, s_dst: jnp.ndarray,
                  table: jnp.ndarray, mask: jnp.ndarray,
                  rows: Optional[jnp.ndarray] = None, *,
                  negative_slope: float = 0.2, self_loop: bool = False,
                  fused: bool = False) -> jnp.ndarray:
    """GAT attention of each row of a dense index table, per head:
    ``(R, H·F)``.

    ``table``/``mask (R, w)`` hold row r's neighbour slots; ``rows (R,)``
    its node id (``None``: row r is node r).  Slot ``i ← j`` scores
    ``LeakyReLU(s_dst[i] + s_src[j])``; with ``self_loop`` node i itself is
    one more slot of its row (a column of the call's table, not an edge of
    the graph).  A masked softmax over the slots weights ``z``'s rows
    through :func:`repro.kernels.ops.edge_softmax_aggregate`: the Pallas
    kernel gathers them with ``fused``, the chunked XLA sum without; no
    ``(R, w, H·F)`` slab is held either way.
    """
    r = table.shape[0]
    if rows is None:
        rows = jnp.arange(r, dtype=jnp.int32)
    if self_loop:
        table = jnp.concatenate([rows[:, None].astype(table.dtype), table],
                                axis=1)
        mask = jnp.concatenate([jnp.ones((r, 1), mask.dtype), mask], axis=1)
    if table.shape[1] == 0:
        return jnp.zeros((r, z.shape[1]), z.dtype)
    from repro.kernels.ops import edge_softmax_aggregate
    e = jax.nn.leaky_relu(s_dst[rows][:, None, :] + s_src[table],
                          negative_slope)                         # (R, w, H)
    return edge_softmax_aggregate(e, mask, z, table, fused=fused)


# --------------------------------------------------------------------------
# Degree-bucket primitives (bucketed layout)
# --------------------------------------------------------------------------
def _bucketed(buckets: DegreeBuckets, reduce) -> jnp.ndarray:
    """``reduce(tab_b, mask_b, rows_b)`` per bucket, back in node order.
    ``rows_b`` are a bucket's node ids; a width-0 bucket's rows sum nothing
    and come out zero, as an all-padding row of the single table does."""
    parts, start = [], 0
    for tab, mask in zip(buckets.tables, buckets.masks):
        rows = buckets.order[start:start + tab.shape[0]]
        parts.append(reduce(tab, mask, rows))
        start += tab.shape[0]
    if len(parts) == 1:              # one bucket holds every node in order
        return parts[0]
    return jnp.concatenate(parts)[buckets.slot_of]


def bucketed_mean_aggregate(h: jnp.ndarray,
                            buckets: DegreeBuckets) -> jnp.ndarray:
    """The padded mean over each bucket's ``w_b`` slots: masked sum over
    the mask sum, as the single table computes it."""
    def reduce(tab, mask, rows):
        s = jnp.einsum("nfd,nf->nd", h[tab], mask)
        return s / jnp.clip(mask.sum(-1, keepdims=True), 1.0, None)
    return _bucketed(buckets, reduce)


def bucketed_sym_aggregate(h: jnp.ndarray, buckets: DegreeBuckets,
                           normalizers: jnp.ndarray) -> jnp.ndarray:
    """The padded ``Σ_j h_j · nrm_i · nrm_j`` over each bucket's slots."""
    def reduce(tab, mask, rows):
        coef = mask * normalizers[tab] * normalizers[rows][:, None]
        return jnp.einsum("nfd,nf->nd", h[tab], coef)
    return _bucketed(buckets, reduce)


def bucketed_gat_aggregate(z: jnp.ndarray, s_src: jnp.ndarray,
                           s_dst: jnp.ndarray, buckets: DegreeBuckets,
                           **kw) -> jnp.ndarray:
    """:func:`gat_aggregate` over each bucket's ``w_b`` slots, in node
    order: the full-neighbor attention without the single table."""
    return _bucketed(buckets, lambda tab, mask, rows: gat_aggregate(
        z, s_src, s_dst, tab, mask, rows, **kw))


# --------------------------------------------------------------------------
# Pallas BCSR primitives (bcsr_kernel layout)
# --------------------------------------------------------------------------
def _bcsr_run(block_d, interpret, x, cols, vals):
    from repro.kernels.spmm import spmm_bcsr
    n, d = x.shape
    n_pad = vals.shape[0] * vals.shape[2]       # n_rb · BM
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, n_pad - n), (0, (-d) % block_d)))
    out = spmm_bcsr(cols, vals, xp, block_d=block_d, interpret=interpret)
    return out[:n, :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bcsr_mv(block_d, interpret, x, cols, vals):
    return _bcsr_run(block_d, interpret, x, cols, vals)


def _bcsr_mv_fwd(block_d, interpret, x, cols, vals):
    out = _bcsr_mv(block_d, interpret, x, cols, vals)
    return out, (x, cols, vals)


def _bcsr_mv_bwd(block_d, interpret, res, g):
    x, cols, vals = res
    gx = _bcsr_run(block_d, interpret, g, cols, vals).astype(x.dtype)
    # tile values are structural operands like the neighbor table — only h
    # carries gradient
    return (gx, np.zeros(np.shape(cols), jax.dtypes.float0),
            jnp.zeros_like(vals))


_bcsr_mv.defvjp(_bcsr_mv_fwd, _bcsr_mv_bwd)


def bcsr_matvec(h: jnp.ndarray, ops: BCSROps) -> jnp.ndarray:
    """``A @ h`` through the Pallas BCSR SpMM, dtype-preserving.

    The adjacency is symmetric, so the ``custom_vjp`` backward is the SAME
    kernel on the SAME tiles applied to the cotangent — no transposed
    operand build, no dense-table gradient.
    """
    from repro.kernels.ops import pallas_interpret
    d = h.shape[1]
    block_d = 128 if d >= 128 else max(8, 1 << (d - 1).bit_length())
    return _bcsr_mv(block_d, pallas_interpret(), h, ops.cols,
                    ops.vals).astype(h.dtype)


def bcsr_mean_aggregate(h: jnp.ndarray, ops: BCSROps) -> jnp.ndarray:
    return bcsr_matvec(h, ops) * ops.inv_deg[:, None].astype(h.dtype)


def bcsr_sym_aggregate(h: jnp.ndarray, ops: BCSROps,
                       normalizers: jnp.ndarray) -> jnp.ndarray:
    nrm = normalizers.astype(h.dtype)[:, None]
    return bcsr_matvec(h * nrm, ops) * nrm
