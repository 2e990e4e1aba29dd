"""Fused masked edge-softmax aggregation (GAT hotspot).

GAT's inner loop is: per node, a masked softmax over ≤F neighbor scores
followed by the weighted sum of the F gathered neighbor embeddings.  Left to
XLA this materializes the (N, F) attention matrix and the (N, F, D) gathered
values in HBM between ops; the kernel fuses softmax + contraction so the
(F × D) slab per node block lives only in VMEM.

Grid: (N/BN_rows, D/BD).  Per step the kernel sees
  scores (BN, F), mask (BN, F), vals (BN, F, BD) → out (BN, BD).
F (the fanout) is kept whole — it is bounded by the sampler (≤ a few dozen)
and the softmax needs the full row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def _edge_softmax_kernel(scores_ref, mask_ref, vals_ref, out_ref):
    s = scores_ref[...].astype(jnp.float32)          # (BN, F)
    m = mask_ref[...]
    s = jnp.where(m > 0, s, -1e30)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s) * m
    denom = jnp.clip(jnp.sum(e, axis=-1, keepdims=True), 1e-30, None)
    alpha = e / denom                                # (BN, F)
    v = vals_ref[...].astype(jnp.float32)            # (BN, F, BD)
    # broadcast-multiply + sum over F, not a batched dot_general: Mosaic
    # has no lowering for a dot with a batch dim here, and with F ≤ a few
    # dozen the contraction is VPU work either way
    out_ref[...] = jnp.sum(alpha[:, :, None] * v, axis=1)


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def edge_softmax(scores: jnp.ndarray, mask: jnp.ndarray, vals: jnp.ndarray,
                 *, interpret: bool, block_n: int = 128,
                 block_d: int = 128) -> jnp.ndarray:
    """out[n] = Σ_f softmax_f(scores[n,·])·vals[n,f,:], masked.

    scores/mask: (N, F); vals: (N, F, D).  N % block_n == 0, D % block_d == 0
    (callers pad; `ops.edge_softmax_aggregate` does this automatically).
    """
    n, f = scores.shape
    d = vals.shape[-1]
    assert n % block_n == 0 and d % block_d == 0
    grid = (n // block_n, d // block_d)
    return pl.pallas_call(
        _edge_softmax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, f), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, f), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, f, block_d), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(scores, mask, vals)
