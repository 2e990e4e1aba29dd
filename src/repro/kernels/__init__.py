"""Pallas TPU kernels for the compute hot spots.

* :mod:`repro.kernels.spmm`         — block-sparse (BCSR) SpMM for full-graph
  neighbor aggregation (the GNN hotspot; used by server correction / GGS).
* :mod:`repro.kernels.edge_softmax` — GAT's attention-weighted sum of
  each row's neighbour rows, per head, gathered by the kernel itself.
* :mod:`repro.kernels.linear_scan`  — chunked linear-attention/SSM scan with
  data-dependent vector decay (Mamba2 SSD and RWKV6 share this core).
* :mod:`repro.kernels.quantize`     — row-wise stochastic-rounding int8
  quantize/dequantize (the compressed-communication wire format).
* :mod:`repro.kernels.ref`          — pure-jnp oracles for all of the above.
* :mod:`repro.kernels.ops`          — jit'd public wrappers; the kernels
  run interpreted on the CPU backend and compiled on a TPU.

All kernels use explicit BlockSpec VMEM tiling with (8,128)-aligned blocks
and are validated against the oracles in interpret mode (tests sweep shapes
and dtypes).
"""
from repro.kernels.ops import (
    spmm_aggregate,
    edge_softmax_aggregate,
    linear_scan,
    quantize_int8_rows,
    dequantize_int8_rows,
)

__all__ = ["spmm_aggregate", "edge_softmax_aggregate", "linear_scan",
           "quantize_int8_rows", "dequantize_int8_rows"]
