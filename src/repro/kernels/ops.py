"""Public jit'd wrappers around the Pallas kernels.

Every op takes unpadded, natural-layout inputs, handles padding/alignment,
and dispatches to the kernel: interpreted on the CPU backend, compiled
everywhere else (see :func:`pallas_interpret`).  The matching oracle from
:mod:`repro.kernels.ref` defines the semantics; ``use_ref=True`` forces the
oracle path (used by equivalence tests and as an escape hatch) — no op
swaps it in without being asked.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.graph.csr import CSRGraph
from repro.kernels import ref
from repro.kernels.edge_softmax import edge_softmax
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
# GAT fused edge softmax
# --------------------------------------------------------------------------
def edge_softmax_aggregate(scores: jnp.ndarray, mask: jnp.ndarray,
                           vals: jnp.ndarray, use_ref: bool = False,
                           block_n: int = 128, block_d: int = 128) -> jnp.ndarray:
    """out[n] = Σ_f softmax_f(scores)·vals — fused GAT aggregation.

    Computes in f32 inside the kernel, returns ``vals.dtype`` so the op is
    dtype-preserving and call sites need no cast.
    """
    n, f = scores.shape
    d = vals.shape[-1]
    if use_ref:
        return ref.edge_softmax_ref(scores, mask, vals).astype(vals.dtype)
    bn = min(block_n, max(8, 1 << (n - 1).bit_length()))
    bd = min(block_d, max(8, 1 << (d - 1).bit_length()))
    s = _pad_to(scores, 0, bn)
    m = _pad_to(mask, 0, bn)
    v = _pad_to(_pad_to(vals, 0, bn), 2, bd)
    out = edge_softmax(s, m, v, block_n=bn, block_d=bd,
                       interpret=pallas_interpret())
    return out[:n, :d].astype(vals.dtype)


@jax.custom_vjp
def edge_softmax_aggregate_trainable(scores, mask, vals):
    """Differentiable fused edge-softmax: Pallas kernel forward, oracle-VJP
    backward — the standard pattern for kernels without a hand-written
    backward.  Used by the GNN GAT layer when ``fused_gat=True``."""
    return edge_softmax_aggregate(scores, mask, vals)


def _esa_fwd(scores, mask, vals):
    return edge_softmax_aggregate(scores, mask, vals), (scores, mask, vals)


def _esa_bwd(res, g):
    scores, mask, vals = res
    _, vjp = jax.vjp(ref.edge_softmax_ref, scores, mask, vals)
    ds, dm, dv = vjp(g.astype(jnp.float32))
    # the oracle computes in f32; cotangents must match the primal dtypes
    return (ds.astype(scores.dtype), jnp.zeros_like(mask),
            dv.astype(vals.dtype))


edge_softmax_aggregate_trainable.defvjp(_esa_fwd, _esa_bwd)


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
