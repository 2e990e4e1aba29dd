"""Block-sparse (BCSR) SpMM Pallas kernel — the GNN aggregation hotspot.

GPU systems implement neighbor aggregation as CSR SpMM with a warp per row
and shared-memory staging.  That design has no TPU analogue (no warps, no
scatter-friendly shared memory); the TPU-native adaptation is **tile-dense,
block-sparse**: the normalized adjacency Â is cut into (BM × BN) dense
tiles, only nonempty tiles are kept (BCSR), and the MXU contracts whole
tiles against (BN × BD) feature slabs staged in VMEM.  Degree-skew is
absorbed by the tile inventory instead of thread divergence.

Layout (host-built by :func:`build_bcsr`):

  tile_cols: (n_row_blocks, max_tiles)            int32  — column-block ids,
             padded with 0 (padding tiles have all-zero values).
  tile_vals: (n_row_blocks, max_tiles, BM, BN)    f32    — tile contents.

Kernel grid: ``(n_row_blocks, n_d_blocks, max_tiles)`` with the tile axis
innermost; ``tile_cols`` rides in scalar-prefetch memory so the feature
BlockSpec can select the right (BN × BD) slab of H per tile.  The output
block is revisited across the k axis and accumulated in VMEM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.graph.csr import CSRGraph


# --------------------------------------------------------------------------
# Host-side BCSR construction
# --------------------------------------------------------------------------
def build_bcsr(graph: CSRGraph, block_m: int = 8, block_n: int = 128,
               normalization: str = "mean") -> Tuple[np.ndarray, np.ndarray, int]:
    """Build (tile_cols, tile_vals, n_padded) from a CSR graph.

    ``normalization``: 'mean' → Â = D⁻¹A (Eq. 1's mean aggregation);
    'sym' → D^{-1/2} A D^{-1/2}; 'none' → raw adjacency.
    """
    n = graph.num_nodes
    # lcm padding so both row and col blocks divide
    lcm = int(np.lcm(block_m, block_n))
    n_pad = int(np.ceil(n / lcm)) * lcm
    assert n_pad % block_m == 0 and n_pad % block_n == 0 and n_pad >= n
    src, dst = graph.to_edges()
    deg = np.maximum(graph.degrees(), 1).astype(np.float32)
    if normalization == "mean":
        vals = 1.0 / deg[src]
    elif normalization == "sym":
        vals = 1.0 / np.sqrt(deg[src] * deg[dst])
    elif normalization == "none":
        vals = np.ones_like(src, dtype=np.float32)
    else:
        raise ValueError(normalization)

    rb = src // block_m
    cb = dst // block_n
    n_rb = n_pad // block_m
    # group edges by (row_block, col_block)
    key = rb.astype(np.int64) * (n_pad // block_n) + cb
    order = np.argsort(key, kind="stable")
    src, dst, vals, rb, cb, key = (a[order] for a in (src, dst, vals, rb, cb, key))
    uniq, starts = np.unique(key, return_index=True)
    starts = list(starts) + [len(key)]

    tiles_per_row: list[list[tuple[int, np.ndarray]]] = [[] for _ in range(n_rb)]
    for u_idx, u in enumerate(uniq):
        lo, hi = starts[u_idx], starts[u_idx + 1]
        r, c = int(u) // (n_pad // block_n), int(u) % (n_pad // block_n)
        tile = np.zeros((block_m, block_n), np.float32)
        tile[src[lo:hi] % block_m, dst[lo:hi] % block_n] = vals[lo:hi]
        # note: duplicate (i,j) edges were deduped in CSRGraph.from_edges
        tiles_per_row[r].append((c, tile))

    max_tiles = max((len(t) for t in tiles_per_row), default=1) or 1
    tile_cols = np.zeros((n_rb, max_tiles), np.int32)
    tile_vals = np.zeros((n_rb, max_tiles, block_m, block_n), np.float32)
    for r, tiles in enumerate(tiles_per_row):
        for k, (c, tile) in enumerate(tiles):
            tile_cols[r, k] = c
            tile_vals[r, k] = tile
    return tile_cols, tile_vals, n_pad


# --------------------------------------------------------------------------
# Kernel
# --------------------------------------------------------------------------
def _spmm_kernel(cols_ref, vals_ref, h_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = vals_ref[0, 0]                       # (BM, BN)
    slab = h_ref[...]                           # (BN, BD)
    out_ref[...] += jnp.dot(tile, slab, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def spmm_bcsr(tile_cols: jnp.ndarray, tile_vals: jnp.ndarray, h: jnp.ndarray,
              *, interpret: bool, block_d: int = 128) -> jnp.ndarray:
    """Â @ H over the BCSR layout.  h: (n_pad, D) with D % block_d == 0."""
    n_rb, max_t, bm, bn = tile_vals.shape
    n_pad, d = h.shape
    assert n_pad % bn == 0, "feature rows must be padded to the column block"
    assert d % block_d == 0, f"D={d} must be a multiple of block_d={block_d}"
    n_db = d // block_d

    grid = (n_rb, n_db, max_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bm, bn), lambda i, j, k, cols: (i, k, 0, 0)),
            pl.BlockSpec((bn, block_d), lambda i, j, k, cols: (cols[i, k], j)),
        ],
        out_specs=pl.BlockSpec((bm, block_d), lambda i, j, k, cols: (i, j)),
    )
    return pl.pallas_call(
        _spmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rb * bm, d), jnp.float32),
        interpret=interpret,
    )(tile_cols, tile_vals, h)
