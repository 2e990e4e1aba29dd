"""Chunked gated linear scan — the Mamba2 SSD / RWKV6 compute core.

Recurrence (per batch·head):   h_t = diag(w_t) h_{t−1} + k_t v_tᵀ,
                               y_t = h_tᵀ q_t,
with data-dependent decay w_t = exp(log_w_t) ∈ (0,1], h ∈ R^{dk×dv}.
Mamba2's SSD is the scalar-decay special case (log_w broadcast over dk);
RWKV6 ("Finch") uses the full vector decay.

A sequential scan is memory-bound and serial in T.  The TPU-native chunked
form splits T into chunks of L, runs the *intra-chunk* part as dense
(L×L)·(L×dv) MXU matmuls and carries only the (dk×dv) state across chunks:

  P_t   = Π_{u≤t} w_u                      (within-chunk cumulative decay)
  A[t,s] = (q_t ⊙ P_t)·(k_s ⊘ P_s),  s ≤ t   → y_intra = tril(A) @ V
  y_inter[t] = (q_t ⊙ P_t)ᵀ h_in
  h_out = diag(P_L) h_in + (K ⊘ P ⊙ P_L)ᵀ V

The kernel's grid is (batch·heads, n_chunks) with the chunk axis innermost
and sequential; the state lives in a VMEM scratch that persists across grid
steps.  f32 with L ≤ 64 keeps the P ratios inside safe exponent range
(|log_w| per step is clamped upstream by the models).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_scan_kernel(strict: bool):
    """Kernel factory.  strict=False → Mamba2 convention (y_t reads h_t);
    strict=True → RWKV6 convention (y_t reads h_{t−1} + the u-bonus for the
    current token)."""

    def kernel(q_ref, k_ref, v_ref, lw_ref, h0_ref, u_ref, y_ref, hT_ref,
               h_scr):
        c = pl.program_id(1)
        n_chunks = pl.num_programs(1)

        @pl.when(c == 0)
        def _load_initial_state():
            h_scr[...] = h0_ref[0]

        q = q_ref[0].astype(jnp.float32)          # (L, dk)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)          # (L, dv)
        lw = lw_ref[0].astype(jnp.float32)        # (L, dk)
        L = q.shape[0]

        # log P_t as a lower-triangular matmul: Mosaic has no cumsum
        row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        tril = jnp.where(row >= col, 1.0, 0.0).astype(jnp.float32)
        lw_cum = jnp.dot(tril, lw, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)
        p = jnp.exp(lw_cum)
        pinv = jnp.exp(-lw_cum)
        # strict: the query sees h_{t-1} ⇒ decay product P_{t-1}
        p_q = jnp.exp(lw_cum - lw) if strict else p
        qp = q * p_q                              # (L, dk)
        kp = k * pinv

        h_in = h_scr[...]                         # (dk, dv)
        attn = jnp.dot(qp, kp.T, preferred_element_type=jnp.float32)  # (L,L)
        attn = jnp.where(row > col if strict else row >= col, attn, 0.0)
        y = jnp.dot(attn, v, preferred_element_type=jnp.float32)
        y += jnp.dot(qp, h_in, preferred_element_type=jnp.float32)
        if strict:
            u = u_ref[0].astype(jnp.float32)      # (1, dk)
            bonus = jnp.sum(q * u * k, axis=1, keepdims=True)   # (L, 1)
            y += bonus * v
        y_ref[0] = y

        # P_L as a row (scales K) and as a column (scales h's dk rows),
        # reduced directly: Mosaic lowers neither p[-1] nor a 1-D transpose
        p_row = jnp.exp(jnp.sum(lw, axis=0, keepdims=True))     # (1, dk)
        p_col = jnp.exp(jnp.sum(lw.T, axis=1, keepdims=True))   # (dk, 1)
        h_out = p_col * h_in + jnp.dot(
            (kp * p_row).T, v, preferred_element_type=jnp.float32)
        h_scr[...] = h_out

        @pl.when(c == n_chunks - 1)
        def _write_final_state():
            hT_ref[0] = h_out

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret", "strict"))
def linear_scan_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        log_w: jnp.ndarray, h0: jnp.ndarray,
                        u: jnp.ndarray | None = None,
                        *, interpret: bool, chunk: int = 64,
                        strict: bool = False):
    """Batched chunked scan.

    q,k,log_w: (BH, T, dk); v: (BH, T, dv); h0: (BH, dk, dv);
    u: (BH, dk) strict-mode bonus (RWKV6); T % chunk == 0.
    Returns (y (BH,T,dv) f32, h_T (BH,dk,dv) f32).
    """
    bh, t, dk = q.shape
    dv = v.shape[-1]
    assert t % chunk == 0, f"T={t} must be a multiple of chunk={chunk}"
    n_chunks = t // chunk
    if u is None:
        u = jnp.zeros((bh, dk), jnp.float32)
    # (BH, 1, dk): a (1, dk) block of a 2-D (BH, dk) operand is neither
    # (8, 128)-aligned nor full-extent, which Mosaic refuses
    u = u.reshape(bh, 1, dk)

    grid = (bh, n_chunks)
    y, hT = pl.pallas_call(
        _make_scan_kernel(strict),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, log_w, h0, u)
    return y, hT
