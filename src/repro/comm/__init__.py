"""Inter-machine communication: the pluggable payload-compression layer.

* :mod:`repro.comm.compress` — codecs (``none | bf16 | int8 | int8_ef``)
  for the two collectives that define LLCG's cost model: the averaging
  round's parameter-delta exchange and the halo round's cut-node feature
  ``all_gather``.  Includes the wire-format byte pricing used by
  ``PlanTrainer.accounting()`` / ``HaloProgram`` / the dryrun HLO
  cross-check.
"""
from repro.comm.compress import (
    COMPRESSIONS,
    HALO_COMPRESSIONS,
    averaging_payload_bytes,
    check_compression,
    compress_features,
    compress_tree,
    decompress_features,
    decompress_tree,
    wire_row_bytes,
)

__all__ = [
    "COMPRESSIONS",
    "HALO_COMPRESSIONS",
    "averaging_payload_bytes",
    "check_compression",
    "compress_features",
    "compress_tree",
    "decompress_features",
    "decompress_tree",
    "wire_row_bytes",
]
