"""Chunked gated linear scan — shared by Mamba2 (SSD) and RWKV6.

Same math as the Pallas kernel (:mod:`repro.kernels.linear_scan`) but
vectorized pure-jnp with a ``lax.scan`` over chunks, which keeps the lowered
HLO compact for the dry-run / pjit path.  Two output conventions:

* ``strict=False`` (Mamba2):  y_t = h_tᵀ q_t          (includes k_t v_tᵀ)
* ``strict=True``  (RWKV6):   y_t = h_{t−1}ᵀ r_t + (r_t·(u⊙k_t))·v_t
  (the current token enters only through the learned "bonus" u).

The Pallas kernel is bit-equivalent to the non-strict path and can be
switched in with ``use_pallas=True`` on real TPUs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def chunked_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 log_w: jnp.ndarray, h0: Optional[jnp.ndarray] = None,
                 chunk: int = 64, strict: bool = False,
                 u: Optional[jnp.ndarray] = None,
                 use_pallas: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q,k,log_w: (BH, T, dk); v: (BH, T, dv); u: (BH, dk) bonus (strict only).

    Returns (y (BH,T,dv) f32, h_T (BH,dk,dv) f32).
    """
    if use_pallas:
        from repro.kernels.ops import linear_scan
        return linear_scan(q, k, v, log_w, h0, chunk=chunk, strict=strict,
                           u=u)

    bh, t, dk = q.shape
    dv = v.shape[-1]
    if t % chunk:
        pad = chunk - t % chunk
        zq = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        q, k, v, log_w = zq(q), zq(k), zq(v), zq(log_w)
    tp = q.shape[1]
    nc = tp // chunk

    def split(x):
        return x.reshape(bh, nc, chunk, -1).astype(jnp.float32).transpose(1, 0, 2, 3)

    qc, kc, vc, lwc = split(q), split(k), split(v), split(log_w)
    if h0 is None:
        h0 = jnp.zeros((bh, dk, dv), jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = (row >= col) if not strict else (row > col)

    def body(h, xs):
        qx, kx, vx, lwx = xs                     # (BH, L, dk/dv)
        lw_cum = jnp.cumsum(lwx, axis=1)         # log P_t  (BH, L, dk)
        p = jnp.exp(lw_cum)
        pinv = jnp.exp(-lw_cum)
        if strict:
            # P_shift_t = P_{t-1} (P_0 = 1)
            p_q = jnp.exp(lw_cum - lwx)
        else:
            p_q = p
        qp = qx * p_q
        kp = kx * pinv
        attn = jnp.einsum("btd,bsd->bts", qp, kp)
        attn = jnp.where(mask[None], attn, 0.0)
        y = jnp.einsum("bts,bsd->btd", attn, vx)
        y = y + jnp.einsum("btd,bdv->btv", qp, h)
        p_last = p[:, -1]                        # (BH, dk)
        h = p_last[:, :, None] * h + jnp.einsum(
            "bsd,bsv->bdv", kp * p_last[:, None, :], vx)
        return h, y

    hT, ys = jax.lax.scan(body, h0.astype(jnp.float32), (qc, kc, vc, lwc))
    y = ys.transpose(1, 0, 2, 3).reshape(bh, tp, dv)[:, :t]
    if strict and u is not None:
        bonus = jnp.einsum("btd,btd->bt",
                           q.astype(jnp.float32)[:, :t],
                           u[:, None, :] * k.astype(jnp.float32)[:, :t])
        y = y + bonus[..., None] * v.astype(jnp.float32)[:, :t]
    return y, hT


def scan_decode_step(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     log_w: jnp.ndarray, h: jnp.ndarray,
                     strict: bool = False, u: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token recurrence.  q,k,log_w: (BH, dk); v: (BH, dv);
    h: (BH, dk, dv).  Returns (y (BH, dv), h')."""
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    w = jnp.exp(log_w.astype(jnp.float32))
    if strict:
        y = jnp.einsum("bd,bdv->bv", q32, h)
        if u is not None:
            y = y + jnp.einsum("bd,bd->b", q32, u * k32)[:, None] * v32
        h = w[:, :, None] * h + k32[:, :, None] * v32[:, None, :]
    else:
        h = w[:, :, None] * h + k32[:, :, None] * v32[:, None, :]
        y = jnp.einsum("bd,bdv->bv", q32, h)
    return y, h
