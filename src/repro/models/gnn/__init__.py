from repro.models.gnn.agg import (
    LAYOUTS,
    AggOperands,
    build_agg_operands,
    choose_layout,
)
from repro.models.gnn.layers import (
    gcn_layer,
    sage_layer,
    gat_layer,
    linear_layer,
    batch_norm,
    mean_aggregate,
    sym_aggregate,
)
from repro.models.gnn.model import (
    GNNModel,
    build_model,
    init_params,
    cross_entropy,
    f1_micro,
)

__all__ = [
    "LAYOUTS",
    "AggOperands",
    "build_agg_operands",
    "choose_layout",
    "gcn_layer",
    "sage_layer",
    "gat_layer",
    "linear_layer",
    "batch_norm",
    "mean_aggregate",
    "sym_aggregate",
    "GNNModel",
    "build_model",
    "init_params",
    "cross_entropy",
    "f1_micro",
]
