"""GAT edge attention: each row's attention-weighted sum of its neighbour
rows, per head, with the neighbour rows gathered by the kernel itself.

    out[r, c] = Σ_j alpha[r, j, head(c)] · z[table[r, j], c]

where column ``c`` of ``z`` belongs to head ``c // head_dim``.  The
attention weights ``alpha (R, W, H)`` are the masked softmax of the edge
scores, computed outside (``repro.kernels.ops.edge_softmax_aggregate``);
``table (R, W)`` holds the neighbour ids.  Left to XLA, ``z[table]`` is an
``(R, W, H·F)`` slab in HBM (8.8 GB per layer for an ogbn-arxiv-size graph
at width 750).  Here it never exists outside VMEM:

* grid over blocks of ``block_rows`` rows; a block's ``(block_rows, W)``
  ids arrive in SMEM, its weights in VMEM, and ``z`` stays in HBM;
* the kernel starts one DMA per slot, ``z[id]`` into the block's
  ``(W, block_rows, SUB, 128)`` VMEM slab (one semaphore per slot column),
  then waits column by column, so later columns land while earlier ones
  are summed;
* ``z`` is laid out ``(N, SUB, 128)``, its ``SUB·128 ≥ H·F`` columns
  folded into whole lane rows: a DMA moves one node's row as a leading-axis
  slice, which the (8, 128) tiling of a 2-D ``(N, D)`` array would refuse.

The arithmetic is float32 multiplies and adds on the VPU: no dot, so no
matmul precision applies.  Lanes past ``H·F`` come out zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: VMEM a block's gathered slab may take; a row's slot holds SUB rows of
#: 128 lanes, padded to the 8-sublane tile
SLAB_VMEM_BYTES = 4 << 20
#: the scoped VMEM the kernel asks of XLA.  Left to XLA's default, on a
#: v5e the kernel inside a whole GAT program gave wrong rows (the 17-slot
#: bucket's in the evaluation; with the ids by scalar prefetch instead, the
#: correction's) while each call alone was right; the cause is not known.
#: With this limit every program read right on the chip.
VMEM_LIMIT_BYTES = 48 << 20


def lane_rows(width: int) -> int:
    """Rows of 128 lanes that hold ``width`` columns."""
    return -(-width // LANES)


def block_rows(slots: int, sub: int) -> int:
    """Rows per grid step: a multiple of 8 between 8 and 128 whose slab of
    ``slots`` gathered rows fits :data:`SLAB_VMEM_BYTES`."""
    per_row = slots * (-(-sub // 8) * 8) * LANES * 4
    return max(8, min(128, SLAB_VMEM_BYTES // max(per_row, 1)) // 8 * 8)


def _kernel(tab_ref, alpha_ref, z_hbm, out_ref, slab, sems, *, heads: int,
            head_dim: int):
    width, rows, sub = slab.shape[0], slab.shape[1], slab.shape[2]

    def copy(j, r, node):
        return pltpu.make_async_copy(z_hbm.at[node], slab.at[j, r],
                                     sems.at[j])

    for j in range(width):
        def start(r, carry, j=j):
            copy(j, r, tab_ref[r, j]).start()
            return carry
        jax.lax.fori_loop(0, rows, start, 0)

    col = (jax.lax.broadcasted_iota(jnp.int32, (1, sub, LANES), 1) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, (1, sub, LANES), 2))
    in_head = [(col >= k * head_dim) & (col < (k + 1) * head_dim)
               for k in range(heads)]
    alpha = alpha_ref[...]                              # (rows, W·H)
    acc = jnp.zeros((rows, sub, LANES), jnp.float32)
    for j in range(width):
        def wait(r, carry, j=j):
            copy(j, r, 0).wait()
            return carry
        jax.lax.fori_loop(0, rows, wait, 0)
        weight = jnp.zeros((rows, sub, LANES), jnp.float32)
        for k in range(heads):
            a = alpha[:, j * heads + k:j * heads + k + 1][:, :, None]
            weight = jnp.where(in_head[k], a, weight)
        acc = acc + weight * slab[j]
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("heads", "head_dim",
                                             "block_rows", "interpret"))
def gat_attention(table: jnp.ndarray, alpha: jnp.ndarray, z: jnp.ndarray,
                  *, heads: int, head_dim: int, block_rows: int,
                  interpret: bool) -> jnp.ndarray:
    """``(R, SUB, 128)`` attention outputs (module docstring).

    table: ``(R, W)`` int32 node ids; alpha: ``(R, W·H)`` float32, slot-major
    (``alpha[r, j·H + k]``); z: ``(N, SUB, 128)`` float32, in HBM.  ``R`` is
    a multiple of ``block_rows``, a multiple of 8; callers pad.
    """
    r, width = table.shape
    sub = z.shape[1]
    assert r % block_rows == 0 and alpha.shape == (r, width * heads)
    kernel = functools.partial(_kernel, heads=heads, head_dim=head_dim)
    return pl.pallas_call(
        kernel,
        grid=(r // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, width), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, width * heads), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_rows, sub, LANES),
                               lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, sub, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((width, block_rows, sub, LANES),
                                   jnp.float32),
                        pltpu.SemaphoreType.DMA((width,))],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="gat_attention",
        interpret=interpret,
    )(table, alpha, z)
