"""Pure-jnp oracles for every kernel in this package.

These are the *definitions of correctness*: simple, obviously-right
implementations with no tiling, used by the kernel tests
(``tests/test_kernels.py`` sweeps shapes/dtypes and asserts allclose) and as
the CPU fallback paths in production code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# SpMM: block-sparse A @ H  (A is (N, N) normalized adjacency)
# --------------------------------------------------------------------------
def spmm_dense_ref(a_dense: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """Dense reference: A @ H."""
    return a_dense.astype(jnp.float32) @ h.astype(jnp.float32)


def spmm_bcsr_ref(tile_cols: jnp.ndarray, tile_vals: jnp.ndarray,
                  h: jnp.ndarray) -> jnp.ndarray:
    """BCSR reference: same data layout as the kernel, contracted naively.

    tile_cols: (n_row_blocks, max_tiles) int32 — column-block index per tile
               (padding tiles point at block 0 with all-zero values).
    tile_vals: (n_row_blocks, max_tiles, BM, BN) float — dense tile contents.
    h:         (n_col_blocks * BN, D).
    """
    n_rb, max_t, bm, bn = tile_vals.shape
    d = h.shape[-1]
    h_blocks = h.reshape(-1, bn, d)

    def row_block(cols_r, vals_r):
        gathered = h_blocks[cols_r]                   # (max_t, BN, D)
        return jnp.einsum("kmn,knd->md", vals_r.astype(jnp.float32),
                          gathered.astype(jnp.float32))

    out = jax.vmap(row_block)(tile_cols, tile_vals)   # (n_rb, BM, D)
    return out.reshape(n_rb * bm, d)


# --------------------------------------------------------------------------
# GAT fused masked softmax-weighted aggregation
# --------------------------------------------------------------------------
def edge_softmax_ref(scores: jnp.ndarray, mask: jnp.ndarray,
                     z: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """out[r, k] = Σ_j softmax_j(scores[r, ·, k])·z[table[r, j], k], masked.

    scores: (R, W, H); mask: (R, W) {0,1}; z: (N, H·F), head k's columns
    ``k·F .. (k+1)·F``; table: (R, W) node ids.  Rows with zero mask produce
    zeros (matches the GNN layer semantics).  Gathers the whole
    ``(R, W, H·F)`` slab: the oracle, not the path.
    """
    r, w, h = scores.shape
    s = jnp.where(mask[..., None] > 0, scores.astype(jnp.float32), -1e30)
    alpha = jax.nn.softmax(s, axis=1) * mask[..., None]
    zg = z.astype(jnp.float32)[table].reshape(r, w, h, -1)
    return jnp.sum(alpha[..., None] * zg, axis=1).reshape(r, -1)


# --------------------------------------------------------------------------
# Row-wise symmetric int8 quantization with stochastic rounding
# --------------------------------------------------------------------------
def quantize_int8_rows_ref(x: jnp.ndarray,
                           u: jnp.ndarray | None = None
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Wire format of the compressed communication layer.

    Each row of ``x (R, C)`` is scaled by ``scale[r] = max(|x[r]|, eps)/127``
    and rounded to int8 as ``clip(floor(x/scale + u), -127, 127)``.  With
    ``u ~ U[0,1)`` this is *stochastic* rounding — the dequantized estimate
    ``q·scale`` is unbiased, the property error-feedback averaging relies
    on.  ``u=None`` means a constant 0.5, i.e. deterministic round-half-up
    (used for halo feature compression, which needs no unbiasedness).
    Returns ``(q int8 (R, C), scale float32 (R, 1))``.
    """
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    uu = jnp.full(x.shape, 0.5, jnp.float32) if u is None else u.astype(jnp.float32)
    q = jnp.clip(jnp.floor(x / scale + uu), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def dequantize_int8_rows_ref(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_int8_rows_ref`: ``q·scale`` as float32."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)


# --------------------------------------------------------------------------
# Linear scan (Mamba2 SSD / RWKV6 core)
# --------------------------------------------------------------------------
def linear_scan_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    log_w: jnp.ndarray,
                    h0: jnp.ndarray | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential oracle for the gated linear recurrence.

      h_t = diag(w_t) h_{t-1} + k_t v_tᵀ          (h ∈ R^{dk×dv})
      y_t = h_tᵀ q_t                               (y ∈ R^{dv})

    q,k,log_w: (T, dk); v: (T, dv); w_t = exp(log_w_t) ∈ (0,1].
    Returns (y (T,dv), h_T (dk,dv)).  Mamba2 uses a scalar per-step decay
    broadcast over dk; RWKV6 uses a full vector decay.
    """
    T, dk = q.shape
    dv = v.shape[-1]
    h_init = jnp.zeros((dk, dv), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inputs):
        qt, kt, vt, lwt = inputs
        h = jnp.exp(lwt)[:, None] * h + kt[:, None] * vt[None, :]
        y = h.T @ qt
        return h, y

    hT, ys = jax.lax.scan(step, h_init,
                          (q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), log_w.astype(jnp.float32)))
    return ys, hT


def linear_scan_batched_ref(q, k, v, log_w, h0=None):
    """vmap of :func:`linear_scan_ref` over a leading (batch·heads) axis."""
    fn = lambda q_, k_, v_, w_, h_: linear_scan_ref(q_, k_, v_, w_, h_)
    if h0 is None:
        h0 = jnp.zeros((q.shape[0], q.shape[-1], v.shape[-1]), jnp.float32)
    return jax.vmap(fn)(q, k, v, log_w, h0)
