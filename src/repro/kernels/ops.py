"""Public jit'd wrappers around the Pallas kernels.

Every op takes unpadded, natural-layout inputs, handles padding/alignment,
and dispatches to the kernel: interpreted on the CPU backend, compiled
everywhere else (see :func:`pallas_interpret`).  The matching oracle from
:mod:`repro.kernels.ref` defines the semantics; ``use_ref=True`` forces the
oracle path (used by equivalence tests and as an escape hatch) — no op
swaps it in without being asked.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph
from repro.kernels import ref
from repro.kernels.edge_softmax import block_rows, gat_attention, lane_rows
from repro.kernels.linear_scan import linear_scan_chunked
from repro.kernels.quantize import dequantize_rows, quantize_rows
from repro.kernels.spmm import build_bcsr, spmm_bcsr


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU backend only.

    Decided at call (trace) time from ``jax.default_backend()``, so
    importing this module initialises no backend, and a TPU never runs the
    interpreter.
    """
    return jax.default_backend() == "cpu"


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --------------------------------------------------------------------------
# SpMM aggregation
# --------------------------------------------------------------------------
def bcsr_device_operands(graph: CSRGraph, block_m: int = 8,
                         block_n: int = 128, normalization: str = "mean"
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Device-resident ``(tile_cols, tile_vals, n_pad)``, built once per
    (graph, block sizes, normalization) and cached on the graph object —
    the same idiom as the host sampling plans
    (:func:`repro.graph.sampling._all_nodes_plan`), so repeated aggregate
    calls never re-pay the host-side :func:`~repro.kernels.spmm.build_bcsr`
    pass or the host→device transfer."""
    cache = graph.__dict__.get("_bcsr_cache")
    if cache is None:
        cache = {}
        object.__setattr__(graph, "_bcsr_cache", cache)
    key = (block_m, block_n, normalization)
    entry = cache.get(key)
    if entry is None:
        tile_cols, tile_vals, n_pad = build_bcsr(graph, block_m, block_n,
                                                 normalization)
        entry = (jnp.asarray(tile_cols), jnp.asarray(tile_vals), n_pad)
        cache[key] = entry
    return entry


def spmm_aggregate(graph: CSRGraph, h: jnp.ndarray,
                   normalization: str = "mean",
                   block_m: int = 8, block_n: int = 128,
                   use_ref: bool = False) -> jnp.ndarray:
    """Full-graph Â @ H via the BCSR kernel. Returns (N, D) in h's dtype."""
    n, d = h.shape
    tile_cols, tile_vals, n_pad = bcsr_device_operands(
        graph, block_m, block_n, normalization)
    h_pad = jnp.pad(h.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    block_d = 128 if d >= 128 else max(8, 1 << (d - 1).bit_length())
    h_pad = _pad_to(h_pad, 1, block_d)
    if use_ref:
        out = ref.spmm_bcsr_ref(tile_cols, tile_vals, h_pad)
    else:
        out = spmm_bcsr(tile_cols, tile_vals, h_pad,
                        block_d=block_d, interpret=pallas_interpret())
    return out[:n, :d].astype(h.dtype)


# --------------------------------------------------------------------------
# GAT edge attention
# --------------------------------------------------------------------------
#: gathered slab bytes one chunk of the XLA attention (the backward, and the
#: forward without the kernel) may hold, per call: a vmap over P machines
#: runs P chunks at once
ATTENTION_CHUNK_BYTES = 32 << 20


def attention_chunk_rows(width: int, cols: int) -> int:
    """Rows per chunk of the XLA attention: their ``(rows, width, cols)``
    float32 slab fits :data:`ATTENTION_CHUNK_BYTES`."""
    return max(1, ATTENTION_CHUNK_BYTES // max(width * cols * 4, 1))


def _chunked(table, alpha, rows: int):
    """``table``/``alpha`` padded to whole chunks of ``rows`` (pad slots
    read node 0 with weight 0) and split ``(n, rows, ...)``."""
    r = table.shape[0]
    n = -(-r // rows)
    pad = n * rows - r
    table = jnp.pad(table, ((0, pad), (0, 0)))
    alpha = jnp.pad(alpha, ((0, pad), (0, 0), (0, 0)))
    return (table.reshape(n, rows, *table.shape[1:]),
            alpha.reshape(n, rows, *alpha.shape[1:]))


def _attend_xla(z, alpha, table):
    """The attention sum in XLA, one chunk of rows at a time: a multiply
    and a sum, exact float32 (no dot)."""
    r, width, heads = alpha.shape
    d = z.shape[1]
    rows = attention_chunk_rows(width, d)

    def chunk(args):
        tab, a = args
        zg = z[tab].reshape(*tab.shape, heads, d // heads)
        return jnp.sum(a[..., None] * zg, axis=1).reshape(tab.shape[0], d)

    if r <= rows:
        return chunk((table, alpha))
    tabs, alphas = _chunked(table, alpha, rows)
    return jax.lax.map(chunk, (tabs, alphas)).reshape(-1, d)[:r]


def _attend_kernel_call(z, alpha, table):
    """The attention sum through the Pallas kernel
    (:func:`repro.kernels.edge_softmax.gat_attention`)."""
    r, width, heads = alpha.shape
    n, d = z.shape
    sub = lane_rows(d)
    z3 = jnp.pad(z.astype(jnp.float32),
                 ((0, 0), (0, sub * 128 - d))).reshape(n, sub, 128)
    bn = block_rows(width, sub)
    pad = (-r) % bn
    tab = jnp.pad(table, ((0, pad), (0, 0)))
    a = jnp.pad(alpha.astype(jnp.float32).reshape(r, width * heads),
                ((0, pad), (0, 0)))
    out = gat_attention(tab, a, z3, heads=heads, head_dim=d // heads,
                        block_rows=bn, interpret=pallas_interpret())
    return out.reshape(r + pad, sub * 128)[:r, :d].astype(z.dtype)


@jax.custom_batching.custom_vmap
def _attend_kernel(z, alpha, table):
    return _attend_kernel_call(z, alpha, table)


@_attend_kernel.def_vmap
def _attend_kernel_vmap(axis_size, in_batched, z, alpha, table):
    """Under vmap (the machines of the local phase) one kernel call over
    every machine's rows: the tables are offset into the machines' stacked
    ``z`` rows."""
    z_b, a_b, t_b = in_batched
    lead = lambda x, b: x if b else jnp.broadcast_to(  # noqa: E731
        x, (axis_size, *x.shape))
    alpha, table = lead(alpha, a_b), lead(table, t_b)
    if z_b:
        n = z.shape[1]
        table = table + (jnp.arange(axis_size, dtype=table.dtype)
                         * n)[:, None, None]
        z = z.reshape(axis_size * n, z.shape[2])
    r = table.shape[1]
    out = _attend_kernel(z, alpha.reshape(axis_size * r, *alpha.shape[2:]),
                         table.reshape(axis_size * r, table.shape[2]))
    return out.reshape(axis_size, r, out.shape[-1]), True


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def attend(z, alpha, table, fused):
    """``out[r, c] = Σ_j alpha[r, j, c // F] · z[table[r, j], c]``: the
    attention-weighted sum of each row's gathered neighbour rows, per head
    (``alpha (R, W, H)``, ``z (N, H·F)``).  The forward runs the Pallas
    kernel with ``fused`` and the chunked XLA sum without; the backward is
    the chunked XLA recompute.  Neither holds more of the gathered slab
    than one chunk (:data:`ATTENTION_CHUNK_BYTES`), the kernel none."""
    return (_attend_kernel if fused else _attend_xla)(z, alpha, table)


def _attend_fwd(z, alpha, table, fused):
    return attend(z, alpha, table, fused), (z, alpha, table)


def _attend_bwd(fused, res, g):
    z, alpha, table = res
    r, width, heads = alpha.shape
    n, d = z.shape
    rows = min(attention_chunk_rows(width, d), r)
    tabs, alphas = _chunked(table, alpha, rows)
    gs = jnp.pad(g, ((0, tabs.shape[0] * rows - r), (0, 0))).reshape(
        tabs.shape[0], rows, heads, d // heads)

    def chunk(dz, args):
        tab, a, gc = args
        zg = z[tab].reshape(*tab.shape, heads, d // heads)
        da = jnp.sum(zg * gc[:, None], axis=-1)                  # (c, W, H)
        upd = (a[..., None] * gc[:, None]).reshape(-1, d)
        return dz.at[tab.reshape(-1)].add(upd), da

    dz, da = jax.lax.scan(chunk, jnp.zeros_like(z), (tabs, alphas, gs))
    da = da.reshape(-1, width, heads)[:r]
    return (dz, da.astype(alpha.dtype),
            np.zeros(np.shape(table), jax.dtypes.float0))


attend.defvjp(_attend_fwd, _attend_bwd)


def edge_softmax_aggregate(scores: jnp.ndarray, mask: jnp.ndarray,
                           z: jnp.ndarray, table: jnp.ndarray,
                           fused: bool = True,
                           use_ref: bool = False) -> jnp.ndarray:
    """GAT aggregation of each table row: the masked softmax of ``scores
    (R, W, H)`` over the row's slots, weighting the slots' rows of ``z
    (N, H·F)`` per head; ``(R, H·F)``.  A row with no valid slot comes out
    zero.  The softmax runs in XLA on the ``(R, W, H)`` scores; the
    weighted sum is :func:`attend` (the kernel with ``fused``)."""
    if use_ref:
        return ref.edge_softmax_ref(scores, mask, z, table).astype(z.dtype)
    valid = mask[..., None] > 0
    alpha = jax.nn.softmax(jnp.where(valid, scores, -1e30), axis=1)
    alpha = alpha * mask[..., None].astype(alpha.dtype)
    return attend(z, alpha.astype(z.dtype), table, fused)


# --------------------------------------------------------------------------
# Row-wise int8 quantize/dequantize (compressed communication wire format)
# --------------------------------------------------------------------------
def quantize_int8_rows(x: jnp.ndarray, u: Optional[jnp.ndarray] = None,
                       use_ref: bool = False, block_r: int = 128
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-wise symmetric int8 quantization with stochastic rounding.

    x: (R, C) float; u: (R, C) uniforms in [0, 1) (None → deterministic
    round-half-up).  Returns ``(q int8 (R, C), scale f32 (R, 1))`` — the
    compressed-communication wire format (1 byte/value + 4 bytes/row).
    """
    r, c = x.shape
    if u is None:
        u = jnp.full((r, c), 0.5, jnp.float32)
    if use_ref:
        return ref.quantize_int8_rows_ref(x, u)
    br = min(block_r, max(8, 1 << (r - 1).bit_length()))
    xp = _pad_to(x.astype(jnp.float32), 0, br)
    up = _pad_to(u.astype(jnp.float32), 0, br)
    vals, scale = quantize_rows(xp, up, block_r=br,
                                interpret=pallas_interpret())
    return vals[:r], scale[:r]


def dequantize_int8_rows(vals: jnp.ndarray, scale: jnp.ndarray,
                         use_ref: bool = False, block_r: int = 128
                         ) -> jnp.ndarray:
    """Inverse of :func:`quantize_int8_rows`: f32 (R, C) ← q·scale."""
    r, c = vals.shape
    if use_ref:
        return ref.dequantize_int8_rows_ref(vals, scale)
    br = min(block_r, max(8, 1 << (r - 1).bit_length()))
    vp = _pad_to(vals, 0, br)
    sp = _pad_to(scale.astype(jnp.float32), 0, br)
    return dequantize_rows(vp, sp, block_r=br,
                           interpret=pallas_interpret())[:r]


# --------------------------------------------------------------------------
# Gated linear scan (Mamba2 / RWKV6)
# --------------------------------------------------------------------------
def linear_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                log_w: jnp.ndarray, h0: Optional[jnp.ndarray] = None,
                chunk: int = 64, use_ref: bool = False,
                strict: bool = False, u: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched gated linear recurrence.

    q,k,log_w: (BH, T, dk); v: (BH, T, dv).  ``strict``/``u`` select the
    RWKV6 output convention (y_t reads h_{t−1} + u-bonus).  Returns (y, h_T).
    A T that is not a multiple of ``chunk`` is zero-padded at the end: the
    padded steps carry k = v = 0 and decay 1, so they leave h_T and the
    real y_t unchanged.
    """
    bh, t, dk = q.shape
    dv = v.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bh, dk, dv), jnp.float32)
    if use_ref:
        if strict:
            from repro.models.transformer.scan_common import chunked_scan
            return chunked_scan(q, k, v, log_w, h0, chunk=chunk,
                                strict=True, u=u)
        return ref.linear_scan_batched_ref(q, k, v, log_w, h0)
    q, k, v, log_w = (_pad_to(x, 1, chunk) for x in (q, k, v, log_w))
    y, h_t = linear_scan_chunked(q, k, v, log_w, h0, u=u, chunk=chunk,
                                 interpret=pallas_interpret(), strict=strict)
    return y[:, :t], h_t
