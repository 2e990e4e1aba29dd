"""Per-machine loss / step / round-body functions shared by every runtime.

:func:`make_loss_fn` is the single loss definition; :func:`make_local_round`
is the K-step local phase (a ``lax.scan``) that the vectorized engine
(:mod:`repro.core.engine`) vmaps across machines and the shard_map runtime
(:mod:`repro.distributed.gnn_sharded`) runs per device.
:func:`halo_fill` is the per-machine half of the engine's ``halo`` round
mode: it splices an all-gathered cut-node feature buffer into one machine's
extended feature rows (:class:`repro.graph.halo.HaloProgram` supplies the
index tables).  :func:`make_machine_step` remains the single-step building
block used by differential tests and micro-benchmarks.  Losses are computed
over a fixed-size batch index vector with a validity weight, so nothing
retraces.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.gnn.agg import row_ids
from repro.models.gnn.model import GNNModel, cross_entropy, f1_micro
from repro.optim.optimizers import Optimizer, apply_updates, masked_update


@dataclasses.dataclass(frozen=True)
class MachineStep:
    """Bundle of compiled functions used by the strategy loops."""

    local_step: Callable
    loss_and_grad: Callable


def make_loss_fn(model: GNNModel) -> Callable:
    """Masked mini-batch cross-entropy on one machine's (padded) view.

    This single definition is the loss of every execution path — the
    per-step simulation loop, the vectorized round engine
    (:mod:`repro.core.engine`), and the shard_map runtime
    (:mod:`repro.distributed.gnn_sharded`) — so backends can be compared
    bit-for-bit.  The forward computes the batch's logits alone
    (``rows=batch``, :meth:`~repro.models.gnn.model.GNNModel.apply`).
    """

    def loss_fn(params, feats, table, mask, batch, labels, bmask, agg=None):
        # ``agg`` threads optional prebuilt aggregation-layout operands
        # (repro.models.gnn.agg) into the forward — the correction phase
        # and serving pass the full-neighbor operands here; the sampled
        # local rounds leave it None (padded path)
        lg = model.apply(params, feats, table, mask, agg=agg, rows=batch)
        lb = labels[batch]
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]
        return (nll * bmask).sum() / jnp.clip(bmask.sum(), 1.0, None)

    return loss_fn


def make_local_round(model: GNNModel, optimizer: Optimizer,
                     reset_opt: bool = True) -> Callable:
    """ONE machine's local phase (Alg. 1/2 lines 3-9) as a ``lax.scan``.

    Returns ``round(params, opt_state, feats, labels, tables, masks,
    batches, bmasks, svalid) -> (params, opt_state, losses)`` where the
    sampled inputs carry a leading K (steps) axis: ``tables (K, N, F)``,
    ``batches (K, B)`` etc.  With ``reset_opt`` the local optimizer is
    freshly initialized from the incoming (server) parameters — line 3 of
    the paper's algorithms; ``reset_opt=False`` threads the state across
    rounds (the centralized / fully-synchronous baselines).

    ``svalid (K,)`` is the per-step validity flag of the engine's
    K-bucketing: steps with ``svalid == 0`` are padding appended to reach a
    bucketed scan length and execute as true no-ops
    (:func:`repro.optim.optimizers.masked_update` — params, step count and
    moments all unchanged); their losses are zeroed.  An all-ones ``svalid``
    makes every step an ordinary ``optimizer.update``.

    This is the shared round body: the simulation backend ``jax.vmap``s it
    across the machine axis, the distributed backend runs it per device
    inside ``shard_map``.
    """
    grad_fn = jax.value_and_grad(make_loss_fn(model))

    def local_round(params, opt_state, feats, labels, tables, masks,
                    batches, bmasks, svalid):
        if reset_opt:
            opt_state = optimizer.init(params)

        def one(carry, xs):
            p, o = carry
            table, mask, batch, bmask, valid = xs
            loss, grads = grad_fn(p, feats, table, mask, batch, labels, bmask)
            upd, o = masked_update(optimizer, grads, o, p, valid)
            return (apply_updates(p, upd), o), loss * valid

        (params, opt_state), losses = jax.lax.scan(
            one, (params, opt_state),
            (tables, masks, batches, bmasks, svalid))
        return params, opt_state, losses

    return local_round


def halo_fill(feats, gathered_flat, recv_idx, dest_idx, recv_valid):
    """Splice exchanged cut-node features into ONE machine's feature rows.

    ``feats (n_ext_pad, d)`` holds only the machine's local rows;
    ``gathered_flat (P · max_send, d)`` is the flattened all-gather of every
    machine's owner-bucketed send buffer.  The machine's halo rows are
    gathered out of it (``recv_idx``) and scattered to their extended-buffer
    destinations (``dest_idx``); padded slots carry ``recv_valid == 0`` and
    a destination of ``n_ext_pad`` — out of bounds, dropped by the scatter —
    so the fill is shape-stable for any halo size up to the mesh-wide max.

    Both engine backends call this: ``shard_map`` on a real
    ``jax.lax.all_gather`` result, ``vmap`` on the same buffer assembled by
    a batched gather — which is what keeps the two differential-testable.
    """
    halo = gathered_flat[recv_idx] * recv_valid[:, None]
    return feats.at[dest_idx].set(halo, mode="drop")


def make_machine_step(model: GNNModel, optimizer: Optimizer) -> MachineStep:
    """Build the jit'd SGD step of Algorithm 1/2 lines 6-8.

    Inputs per call (all fixed-shape):
      feats  (N, d)    local (padded) features
      table  (N, F)    this step's sampled neighbor table
      mask   (N, F)    validity
      batch  (B,)      mini-batch node indices (local)
      labels (N,)      local labels
      bmask  (B,)      1.0 for real batch entries (padding-safe)
    """
    loss_fn = make_loss_fn(model)
    grad_fn = jax.value_and_grad(loss_fn)

    @jax.jit
    def local_step(params, opt_state, feats, table, mask, batch, labels, bmask):
        loss, grads = grad_fn(params, feats, table, mask, batch, labels, bmask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss

    @jax.jit
    def loss_and_grad(params, feats, table, mask, batch, labels, bmask):
        return grad_fn(params, feats, table, mask, batch, labels, bmask)

    return MachineStep(local_step=local_step, loss_and_grad=loss_and_grad)


def make_eval_fn(model: GNNModel) -> Callable:
    """Full-graph, full-neighbor evaluation (the paper's 'global validation
    score' — computed on the server with the complete graph).  ``agg``
    optionally carries prebuilt aggregation operands for the full-neighbor
    table (the degree buckets of :func:`repro.models.gnn.agg.
    bucketed_operands`); ``None`` aggregates over ``table``/``mask``.
    ``nodes`` are the evaluated node ids, or their own neighbour slots
    prebuilt (:func:`repro.models.gnn.agg.degree_buckets` of the nodes);
    the forward computes their logits alone."""

    @jax.jit
    def evaluate(params, feats, table, mask, labels, nodes, agg=None):
        logits = model.apply(params, feats, table, mask, agg=agg, rows=nodes)
        lb = labels[row_ids(nodes)]
        return cross_entropy(logits, lb), f1_micro(logits, lb)

    return evaluate
