"""Pallas tile kernels: row-wise stochastic-rounding int8 quantize/dequantize.

This is the wire format of the compressed communication layer
(:mod:`repro.comm.compress`): each row of a float32 buffer carries a single
f32 scale (``max(|row|)/127``, 4 bytes) plus its values stochastically
rounded to int8 (1 byte each).  The uniforms ``u`` come in as an operand —
generated from the documented ``jax.random`` fold chain by the caller — so
the kernel is a pure function, identical under interpret and compiled
lowering, and exactly matched by the jnp oracles in
:mod:`repro.kernels.ref`.

Grid: (R/BR,).  C (the row width) is kept whole per block — the per-row
max-abs reduction needs the full row, and rows here are either a graph
feature dim or a flattened parameter leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def _quantize_kernel(x_ref, u_ref, vals_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)               # (BR, C)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.floor(x / scale + u_ref[...]), -127.0, 127.0)
    vals_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


def _dequantize_kernel(vals_ref, scale_ref, out_ref):
    out_ref[...] = vals_ref[...].astype(jnp.float32) * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def quantize_rows(x: jnp.ndarray, u: jnp.ndarray, *, interpret: bool,
                  block_r: int = 128
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(q int8 (R, C), scale f32 (R, 1)) ← x (R, C), u (R, C) uniforms.

    R % block_r == 0 (callers pad; ``ops.quantize_int8_rows`` does this
    automatically).
    """
    r, c = x.shape
    assert r % block_r == 0
    grid = (r // block_r,)
    return pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, c), lambda i: (i, 0)),
            pl.BlockSpec((block_r, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, c), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, u)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def dequantize_rows(vals: jnp.ndarray, scale: jnp.ndarray, *,
                    interpret: bool, block_r: int = 128) -> jnp.ndarray:
    """f32 (R, C) ← vals int8 (R, C) · scale f32 (R, 1).  R % block_r == 0."""
    r, c = vals.shape
    assert r % block_r == 0
    grid = (r // block_r,)
    return pl.pallas_call(
        _dequantize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, c), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        interpret=interpret,
    )(vals, scale)
