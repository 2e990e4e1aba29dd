"""GNN model assembly from the paper's architecture strings.

Table 2's "Base Arch." column encodes models as operator strings:
``BSBSBL`` = BatchNorm→SAGE→BatchNorm→SAGE→BatchNorm→Linear, ``GBGBG`` etc.
:func:`build_model` accepts those strings plus the two whole-model variants
``GAT`` and ``APPNP``, and returns a :class:`GNNModel` with ``init``/``apply``.

``apply(params, feats, table, mask) -> logits (N, C)`` computes embeddings
for every node of the given (sub)graph, as each machine materializes its
local hidden state in the paper and the sampled table decides Ñ(v);
``apply(..., rows=r)`` gives the logits of the rows ``r`` alone, which is
what a loss reads (:meth:`GNNModel.top_cut_op`).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn import layers as L
from repro.models.gnn.agg import row_ids, row_slots


def _glorot(rng, shape):
    fan_in, fan_out = shape[0], shape[-1]
    scale = np.sqrt(2.0 / (fan_in + fan_out))
    return (rng.standard_normal(shape) * scale).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class GNNModel:
    arch: str
    feature_dim: int
    hidden_dim: int
    num_classes: int
    appnp_steps: int = 10
    appnp_beta: float = 0.1
    fused_gat: bool = False   # GAT's neighbour gather+sum in the Pallas kernel
    # the GAT stack (``hidden_dim`` is the width of one head): its layers,
    # heads per layer (concatenated between layers, averaged at the last),
    # a residual projection h R per layer, the node itself as one more slot
    # of its attention, and between layers BatchNorm then ReLU (without:
    # a bias then ELU).  The defaults are the two-layer single-head GAT.
    num_layers: int = 2
    num_heads: int = 1
    residual: bool = False
    self_loop: bool = False
    batch_norm: bool = False
    # default aggregation layout for full-graph consumers (serving backends
    # read this when not overridden); "padded" | "csr" | "bcsr_kernel" |
    # "auto" — see repro.models.gnn.agg
    agg_layout: str = "padded"

    def __post_init__(self):
        from repro.models.gnn.agg import LAYOUTS
        if self.agg_layout not in LAYOUTS:
            raise ValueError(f"unknown agg_layout {self.agg_layout!r}; "
                             f"choose one of {LAYOUTS}")
        stack = {"num_layers": 2, "num_heads": 1, "residual": False,
                 "self_loop": False, "batch_norm": False}
        if self.arch != "GAT" and any(getattr(self, k) != v
                                      for k, v in stack.items()):
            raise ValueError(f"{sorted(stack)} shape the GAT stack only, "
                             f"not {self.arch!r}")
        if self.num_layers < 1 or self.num_heads < 1:
            raise ValueError("a GAT stack needs a layer and a head")

    # ------------------------------------------------------------------ init
    def init(self, seed: int = 0) -> Dict:
        rng = np.random.default_rng(seed)
        params: Dict[str, Dict] = {}
        dims = self._dims()
        if self.arch == "GAT":
            return jax.tree_util.tree_map(jnp.asarray, self._init_gat(rng))
        if self.arch == "APPNP":
            d_in, d_h = self.feature_dim, self.hidden_dim
            params["lin0"] = {"w": _glorot(rng, (d_in, d_h)),
                              "b": np.zeros(d_h, np.float32)}
            params["lin1"] = {"w": _glorot(rng, (d_h, self.num_classes)),
                              "b": np.zeros(self.num_classes, np.float32)}
            return jax.tree_util.tree_map(jnp.asarray, params)
        for i, (op, (d_in, d_out)) in enumerate(zip(self.arch, dims)):
            name = f"{op.lower()}{i}"
            if op == "G":
                params[name] = {"w": _glorot(rng, (d_in, d_out)),
                                "b": np.zeros(d_out, np.float32)}
            elif op == "S":
                params[name] = {"w_self": _glorot(rng, (d_in, d_out)),
                                "w_nbr": _glorot(rng, (d_in, d_out)),
                                "b": np.zeros(d_out, np.float32)}
            elif op == "L":
                params[name] = {"w": _glorot(rng, (d_in, d_out)),
                                "b": np.zeros(d_out, np.float32)}
            elif op == "B":
                params[name] = {"gamma": np.ones(d_in, np.float32),
                                "beta": np.zeros(d_in, np.float32)}
            else:
                raise ValueError(f"unknown op {op!r} in arch {self.arch!r}")
        return jax.tree_util.tree_map(jnp.asarray, params)

    def _init_gat(self, rng) -> Dict:
        """Glorot draws per layer in the order ``w`` (d_in, H·F), ``r``
        (the same shape, with ``residual``), ``a_dst`` then ``a_src``
        ((H, F), or (F,) for one head); F is ``hidden_dim``, the classes at
        the last layer.  Between layers ``bn<l>`` (gamma 1, beta 0) with
        ``batch_norm``, else a zero bias ``b``; a zero bias ``b`` at the
        last layer."""
        params: Dict[str, Dict] = {}
        heads, d = self.num_heads, self.feature_dim
        for layer in range(self.num_layers):
            last = layer == self.num_layers - 1
            f = self.num_classes if last else self.hidden_dim
            a_shape = (f,) if heads == 1 else (heads, f)
            p = {"w": _glorot(rng, (d, heads * f))}
            if self.residual:
                p["r"] = _glorot(rng, (d, heads * f))
            p["a_dst"] = _glorot(rng, a_shape)
            p["a_src"] = _glorot(rng, a_shape)
            if last:
                p["b"] = np.zeros(f, np.float32)
            elif self.batch_norm:
                params[f"bn{layer}"] = {
                    "gamma": np.ones(heads * f, np.float32),
                    "beta": np.zeros(heads * f, np.float32)}
            else:
                p["b"] = np.zeros(heads * f, np.float32)
            params[f"gat{layer}"] = p
            d = heads * f
        return params

    def num_message_hops(self) -> int:
        """Graph-aggregation depth L: how far information travels.

        The L-hop receptive field an exact partitioned forward must cover —
        the serving backend sizes its inference halo
        (:func:`repro.graph.halo.build_inference_plan`) from this.
        Linear/BatchNorm ops are pointwise and contribute nothing.
        """
        if self.arch == "GAT":
            return self.num_layers
        if self.arch == "APPNP":
            return self.appnp_steps
        return sum(1 for op in self.arch if op in ("G", "S"))

    def _dims(self) -> List[Tuple[int, int]]:
        """(d_in, d_out) per op; BatchNorm keeps width."""
        dims = []
        d = self.feature_dim
        # find index of last width-changing op → maps to num_classes
        changing = [i for i, op in enumerate(self.arch) if op != "B"]
        last = changing[-1] if changing else len(self.arch) - 1
        for i, op in enumerate(self.arch):
            if op == "B":
                dims.append((d, d))
            else:
                d_out = self.num_classes if i == last else self.hidden_dim
                dims.append((d, d_out))
                d = d_out
        return dims

    def top_cut_op(self) -> Optional[int]:
        """The op at which ``apply(rows=...)`` starts computing the rows
        alone: the last aggregation (a GAT stack's last layer), which
        gathers the rows' slots from the whole ``h`` below it.  ``None``
        where a BatchNorm follows the last aggregation (its statistics
        need every row), and for APPNP (every propagation step mixes every
        row): those select the rows after the last such op."""
        if self.arch == "GAT":
            return self.num_layers - 1
        if self.arch == "APPNP":
            return None
        aggs = [i for i, op in enumerate(self.arch) if op in "GS"]
        return aggs[-1] if aggs and aggs[-1] > self.arch.rfind("B") else None

    # ----------------------------------------------------------------- apply
    def apply(self, params: Dict, feats: jnp.ndarray, table: jnp.ndarray,
              mask: jnp.ndarray, agg=None, rows=None) -> jnp.ndarray:
        """Logits for every node, or with ``rows`` for those rows alone:
        ``apply(..., rows=r)`` is ``apply(...)[r]``.  ``agg`` optionally
        threads prebuilt :class:`repro.models.gnn.agg.AggOperands` into
        every aggregate op (edge-centric / Pallas-kernel layouts for
        full-neighbor tables); ``None`` is the unchanged padded-table path.

        ``rows`` are node ids ``(R,)``, or the rows' own neighbour slots
        prebuilt as :func:`repro.models.gnn.agg.degree_buckets` of those
        nodes.  Ops before :meth:`top_cut_op` run on every row, the
        aggregation at it on the rows' slots (:func:`~repro.models.gnn.agg.
        row_slots`), the ops after it on the rows alone; where the layout
        gives no slots for rows (``csr``), the rows are selected after that
        aggregation instead.
        """
        if self.arch == "GAT":
            return self._apply_gat(params, feats, table, mask, agg, rows)
        if self.arch == "APPNP":
            h = jax.nn.relu(L.linear_layer(params["lin0"], feats))
            h = L.linear_layer(params["lin1"], h)
            h = L.appnp_propagate(h, table, mask, self.appnp_steps,
                                  self.appnp_beta, agg=agg)
            return h if rows is None else h[row_ids(rows)]
        cut, pick, slots = self._cut(table, mask, agg, rows)
        h = feats
        changing = [i for i, op in enumerate(self.arch) if op != "B"]
        last = changing[-1] if changing else len(self.arch) - 1
        for i, op in enumerate(self.arch):
            if i == pick:
                h = h[row_ids(rows)]
            name = f"{op.lower()}{i}"
            act = None if i == last else jax.nn.relu
            at = slots if i == cut else None
            if op == "G":
                h = L.gcn_layer(params[name], h, table, mask, activation=act,
                                agg=agg, rows=at)
            elif op == "S":
                h = L.sage_layer(params[name], h, table, mask, activation=act,
                                 agg=agg, rows=at)
            elif op == "L":
                h = L.linear_layer(params[name], h, activation=act)
            elif op == "B":
                h = L.batch_norm(params[name], h)
        return h[row_ids(rows)] if pick == len(self.arch) else h

    def _cut(self, table, mask, agg, rows):
        """``(cut, pick, slots)`` for :meth:`apply`: the op whose
        aggregation runs on ``slots`` (the rows' slots) and the op before
        which the rows are selected, each ``None`` where it does not
        apply."""
        if rows is None:
            return None, None, None
        cut = self.top_cut_op()
        if cut is None:
            return None, self.arch.rfind("B") + 1, None
        slots = row_slots(rows, table, mask, agg)
        return (cut, None, slots) if slots is not None else (None, cut + 1,
                                                             None)


    def _apply_gat(self, params, h, table, mask, agg, rows):
        """The GAT stack: per layer :func:`~repro.models.gnn.layers.
        gat_layer`; between layers concat → BatchNorm → ReLU with
        ``batch_norm``, else concat → + b → ELU; the last layer's heads
        averaged, + b.  With ``rows``, the last layer on the rows alone."""
        heads = self.num_heads
        cut, pick, slots = self._cut(table, mask, agg, rows)
        for layer in range(self.num_layers):
            p = params[f"gat{layer}"]
            out = L.gat_layer(p, h, table, mask, heads=heads,
                              self_loop=self.self_loop, fused=self.fused_gat,
                              agg=agg, rows=slots if layer == cut else None)
            if layer + 1 == pick:
                out = out[row_ids(rows)]
            if layer == self.num_layers - 1:
                return (out.reshape(out.shape[0], heads, -1).mean(axis=1)
                        + p["b"])
            if self.batch_norm:
                h = jax.nn.relu(L.batch_norm(params[f"bn{layer}"], out))
            else:
                h = jax.nn.elu(out + p["b"])


def build_model(arch: str, feature_dim: int, num_classes: int,
                hidden_dim: int = 64, **kw) -> GNNModel:
    return GNNModel(arch=arch, feature_dim=feature_dim, hidden_dim=hidden_dim,
                    num_classes=num_classes, **kw)


def init_params(model: GNNModel, seed: int = 0) -> Dict:
    return model.init(seed)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """(1/B) Σ_{i∈ξ} φ(h_i^{(L)}, y_i) — Eq. 2/4's mini-batch loss, over
    the batch's logits and labels."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def f1_micro(logits: jnp.ndarray, labels: jnp.ndarray,
             nodes: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Micro-F1 for single-label multiclass == accuracy (paper's metric)."""
    if nodes is not None:
        logits, labels = logits[nodes], labels[nodes]
    return (logits.argmax(-1) == labels).mean()
