"""Pluggable compression codecs for the inter-machine collectives.

LLCG's entire axis of merit is communication cost, and the repo prices it
exactly (``History`` bytes, ``HaloProgram.exchange_bytes``, the dryrun HLO
cross-check) — so compression here changes *what actually crosses the
wire*, and the accounting layer prices the compressed format, never an
estimate.  Two independent knobs on :class:`repro.core.plan.CommSpec`:

``compression``       — averaging rounds.  Each machine compresses its
    parameter *delta* (new params − round input) before the collective;
    the receivers dequantize and average.  ``int8_ef`` additionally
    carries a per-machine error-feedback residual (in
    ``EngineState.comm_residual``): the quantization error of round r is
    added back into the delta of round r+1, so the averaged iterates
    converge to the uncompressed fixed point even though every individual
    message is lossy (the classic EF-SGD argument; stochastic rounding
    makes each message unbiased on top).
``halo_compression``  — halo (GGS) rounds and halo serving.  The cut-node
    feature send buffer is quantized row-wise (one f32 scale per node row)
    before the ``all_gather`` and dequantized after, in both engine
    backends and the serving ``_halo_exchange``.  Features are static
    within a round, so deterministic round-half-up is used — no residual,
    and ``int8_ef`` is not a valid halo codec.

Wire formats priced by :func:`wire_row_bytes` / :func:`averaging_payload_bytes`:

=========  =============================================================
``none``   f32 as-is (byte accounting identical to pre-compression).
``bf16``   values cast to bfloat16 — 2 bytes/value, no side data.
``int8``   stochastic-rounding symmetric int8 — 1 byte/value + one f32
           scale per row (halo: per node row; averaging: per parameter
           leaf per machine).
``int8_ef`` same wire format as ``int8``; the residual never leaves the
           machine so it costs no bytes.
=========  =============================================================

The quantize/dequantize ops are the Pallas tile kernels in
:mod:`repro.kernels.quantize` (interpreted on the CPU backend), with the
jnp oracles in :mod:`repro.kernels.ref` defining the semantics.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import dequantize_int8_rows, quantize_int8_rows

COMPRESSIONS = ("none", "bf16", "int8", "int8_ef")
HALO_COMPRESSIONS = ("none", "bf16", "int8")

# one f32 scale rides with every int8 row
_SCALE_BYTES = 4


def check_compression(name: str, halo: bool = False) -> str:
    """Validate a codec name (the spec-validation idiom of core.plan)."""
    allowed = HALO_COMPRESSIONS if halo else COMPRESSIONS
    if name not in allowed:
        kind = "halo_compression" if halo else "compression"
        raise ValueError(f"{kind} must be one of {allowed}, got {name!r}")
    return name


# --------------------------------------------------------------------------
# Wire-format byte pricing (the single source for accounting/dryrun/serving)
# --------------------------------------------------------------------------
def wire_row_bytes(d: int, dtype=np.float32, compression: str = "none") -> float:
    """Bytes one ``d``-wide feature row occupies on the wire."""
    if compression == "none":
        return float(d * np.dtype(dtype).itemsize)
    if compression == "bf16":
        return float(d * 2)
    return float(d + _SCALE_BYTES)          # int8 values + per-row f32 scale


def averaging_payload_bytes(params: Any, compression: str = "none") -> float:
    """Bytes one machine's compressed parameter delta occupies on the wire.

    Per-leaf scales (one f32 per parameter leaf per machine) for the int8
    codecs; for ``none`` this equals ``utils.pytree.tree_bytes`` exactly so
    uncompressed accounting is bit-identical to pre-compression.
    """
    leaves = jax.tree_util.tree_leaves(params)
    if compression == "none":
        return float(sum(x.size * x.dtype.itemsize for x in leaves))
    if compression == "bf16":
        return float(sum(x.size * 2 for x in leaves))
    return float(sum(x.size + _SCALE_BYTES for x in leaves))


# --------------------------------------------------------------------------
# Parameter-delta codecs (averaging rounds)
# --------------------------------------------------------------------------
def machine_keys(key: jnp.ndarray, num_machines: int) -> jnp.ndarray:
    """Stacked per-machine keys — the same fold the shard backend applies
    via ``jax.lax.axis_index``, so vmap and shard_map draw identical bits."""
    return jax.vmap(lambda m: jax.random.fold_in(key, m))(
        jnp.arange(num_machines, dtype=jnp.uint32))


def compress_tree(delta: Any, compression: str,
                  key: Optional[jnp.ndarray] = None, stacked: bool = False
                  ) -> Tuple[Any, Optional[Any]]:
    """Compress a parameter-delta pytree → ``(payload, scales)``.

    ``stacked=True`` means leaves carry a leading machine axis (the vmap
    backend) and get per-machine scales; ``key`` is then the stacked
    per-machine key array from :func:`machine_keys`.  ``key=None`` falls
    back to deterministic rounding.  ``scales`` is None for ``none``/
    ``bf16``.
    """
    if compression == "none":
        return delta, None
    if compression == "bf16":
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), delta), None
    leaves, treedef = jax.tree_util.tree_flatten(delta)
    payloads, scales = [], []
    for i, leaf in enumerate(leaves):
        rows = leaf.shape[0] if stacked else 1
        flat = leaf.reshape(rows, -1)
        if key is None:
            u = None
        elif stacked:
            u = jax.vmap(lambda k: jax.random.uniform(
                jax.random.fold_in(k, i), (flat.shape[1],)))(key)
        else:
            u = jax.random.uniform(jax.random.fold_in(key, i), flat.shape)
        q, s = quantize_int8_rows(flat, u)
        payloads.append(q.reshape(leaf.shape))
        scales.append(s)
    return (jax.tree_util.tree_unflatten(treedef, payloads),
            jax.tree_util.tree_unflatten(treedef, scales))


def decompress_tree(payload: Any, scales: Optional[Any],
                    compression: str) -> Any:
    """Inverse of :func:`compress_tree` — f32 pytree.  Works for both the
    per-machine and the all-gathered form (rows are read off the scale
    leaf, so a gathered ``(P, …)`` payload dequantizes per machine)."""
    if compression == "none":
        return payload
    if compression == "bf16":
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), payload)

    def leaf(q, s):
        rows = s.size
        out = dequantize_int8_rows(q.reshape(rows, -1), s.reshape(rows, 1))
        return out.reshape(q.shape)

    return jax.tree_util.tree_map(leaf, payload, scales)


# --------------------------------------------------------------------------
# Feature-buffer codecs (halo rounds / halo serving)
# --------------------------------------------------------------------------
def compress_features(x: jnp.ndarray, compression: str
                      ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Compress a ``(rows, d)`` feature send buffer → ``(payload, scales)``.

    Deterministic round-half-up (features are static within a round; halo
    needs no unbiasedness), one f32 scale per row for int8.
    """
    if compression == "none":
        return x, None
    if compression == "bf16":
        return x.astype(jnp.bfloat16), None
    return quantize_int8_rows(x)


def decompress_features(payload: jnp.ndarray,
                        scales: Optional[jnp.ndarray],
                        compression: str) -> jnp.ndarray:
    """Inverse of :func:`compress_features` — f32 ``(rows, d)``.  Accepts
    the gathered ``(…, rows, d)`` form too (flattened to rows)."""
    if compression == "none":
        return payload
    if compression == "bf16":
        return payload.astype(jnp.float32)
    d = payload.shape[-1]
    out = dequantize_int8_rows(payload.reshape(-1, d),
                               scales.reshape(-1, 1))
    return out.reshape(payload.shape)
