"""Neighbor sampling (Hamilton et al., 2017) — Eq. 4 of the paper.

Local machines compute stochastic gradients on mini-batches with *sampled*
neighbors Ñ_p(v) ⊂ N_p(v); the server correction uses *full* neighbors.
Sampling introduces the σ²_bias term of Assumption 1 — the quantity the
correction step exists to cancel — so the sampler is a first-class citizen:
it exposes the sampling ratio (Figure 6 ablation) and produces fixed-shape
``(B, fanout)`` tables that jit cleanly.

Two execution paths produce the same *distribution* of tables:

* **vectorized** (default, ``rng_compat=False``) — batched numpy over the
  CSR arrays: one span gather + one uniform random-keys draw per round
  (:func:`sample_neighbors_batched`), instead of P×K×B Python iterations.
  Rows with degree > fanout are subsampled without replacement by ranking
  i.i.d. uniform keys and keeping the ``fanout`` smallest (degree-aware
  masking makes the padded slots inert).
* **rng_compat** (``rng_compat=True``) — the original per-node
  ``rng.choice`` loop, reproducing the pre-vectorization RNG stream draw
  for draw.  The engine equivalence tests use it to compare new runs
  bit-for-bit against trajectories recorded with the sequential sampler.

Both paths honour the invariants tested in ``tests/test_graph.py``: sampled
entries are a subset of the true neighborhood, drawn without replacement,
and nodes with degree ≤ fanout keep all neighbors (σ²_bias → 0 in the
full-neighbor limit).

**Device-resident sampling.**  A third path moves the whole round draw onto
the accelerator: :func:`build_device_csr` stacks P CSR shards' neighbor rows into a
:class:`DeviceCSR` once, and :func:`sample_round_device` /
:func:`sample_serving_tables_device` produce the same fixed-shape
``(P, K, n_pad, fanout)`` tables as the host paths from ``jax.random``
draws — no host loop, no host→device copy per round, and the sample for
round r+1 can be dispatched while round r's scan still runs (the engine's
double-buffered overlap, ``repro.core.engine.run_schedule``).  The device
RNG stream is documented and replayable:

    round key  = fold_in(base_key, r)                  (caller supplies)
    machine    = fold_in(round_key, p)
    step       = fold_in(machine_key, s)
    neighbors  = bits(fold_in(step_key, 0), (n_pad, dmax))
    batch WOR  = bits(fold_in(step_key, 1), (t_pad,))
    batch WR   = randint(fold_in(step_key, 2), (B,))

Because every step folds its own key, the draw for a real step is
independent of the total scan length — sampling directly at a K-bucketed
padded length reproduces the unbucketed stream bit-for-bit on the real
prefix.  Neighbor subsets are uniform without replacement via the same
random-keys ranking as the host path (threefry bits ranked per row with an
index tie-break, implemented as a pairwise-rank compaction that avoids
XLA's slow ``top_k`` on small widths).  The neighbor ids sit in a per-row
table; on that path the same one-hot compaction picks the sampled ids out
of it, so no gather runs per sampled slot.  Graphs wider than
``_RANK_SELECT_MAX_WIDTH`` rank with ``top_k`` and gather the selected
slots out of the row table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph, gather_neighbor_rows, neighbor_spans

# Bound on the number of uniform keys materialized per vectorized draw
# (steps × oversampled-rows × max-degree); larger rounds chunk the step axis.
_MAX_KEY_ELEMS = 1 << 24


def _sample_neighbors_loop(graph: CSRGraph, nodes: np.ndarray, fanout: int,
                           rng: np.random.Generator
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """Legacy per-node loop — the rng_compat reference stream."""
    n = len(nodes)
    table = np.zeros((n, fanout), dtype=np.int32)
    mask = np.zeros((n, fanout), dtype=np.float32)
    for i, v in enumerate(nodes):
        nbrs = graph.neighbors(int(v))
        if nbrs.size == 0:
            continue
        if nbrs.size <= fanout:
            table[i, : nbrs.size] = nbrs
            mask[i, : nbrs.size] = 1.0
        else:
            sel = rng.choice(nbrs, size=fanout, replace=False)
            table[i] = sel
            mask[i] = 1.0
    return table, mask


@dataclasses.dataclass(frozen=True)
class _SamplingPlan:
    """Round-invariant precomputation for one ``(nodes, fanout)`` pair.

    Splitting keep/over rows, gathering the step-invariant keep-row tables
    and building the degree mask depend only on the graph topology, so for
    the hot all-nodes case they are cached on the graph instance and every
    per-round call reduces to one key draw + one argpartition + one gather.
    """

    num_rows: int
    keep_idx: np.ndarray       # rows with degree ≤ fanout (sampled = full)
    keep_table: np.ndarray     # (n_keep, fanout) step-invariant neighbors
    keep_mask: np.ndarray      # (n_keep, fanout)
    over_idx: np.ndarray       # rows with degree > fanout (subsampled)
    over_starts: np.ndarray    # (n_over,) CSR span starts
    over_dmax: int             # max degree among over rows
    over_invalid: np.ndarray   # (n_over, over_dmax) key slots past the span


def _build_sampling_plan(graph: CSRGraph, nodes: np.ndarray,
                         fanout: int) -> _SamplingPlan:
    nodes = np.asarray(nodes, dtype=np.int64)
    starts, deg = neighbor_spans(graph, nodes)
    keep = deg <= fanout
    k_idx = np.where(keep)[0]
    keep_table, keep_mask = gather_neighbor_rows(graph, nodes[k_idx], fanout)
    o_idx = np.where(~keep)[0]
    if o_idx.size:
        o_deg = deg[o_idx]
        dmax = int(o_deg.max())
        invalid = np.arange(dmax)[None, :] >= o_deg[:, None]
    else:
        dmax, invalid = 0, np.zeros((0, 0), bool)
    return _SamplingPlan(num_rows=nodes.size, keep_idx=k_idx,
                         keep_table=keep_table, keep_mask=keep_mask,
                         over_idx=o_idx, over_starts=starts[o_idx],
                         over_dmax=dmax, over_invalid=invalid)


def _all_nodes_plan(graph: CSRGraph, fanout: int) -> _SamplingPlan:
    """Cached :class:`_SamplingPlan` over all of ``graph``'s nodes."""
    cache = graph.__dict__.get("_sampling_plans")
    if cache is None:
        cache = {}
        object.__setattr__(graph, "_sampling_plans", cache)  # frozen dataclass
    plan = cache.get(fanout)
    if plan is None:
        plan = _build_sampling_plan(graph, np.arange(graph.num_nodes), fanout)
        cache[fanout] = plan
    return plan


def sample_neighbors_batched(graph: CSRGraph, nodes: Optional[np.ndarray],
                             fanout: int, rng: np.random.Generator,
                             num_steps: int = 1
                             ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized sampling of ``num_steps`` independent neighbor tables.

    Returns ``(table, mask)`` of shape ``(num_steps, len(nodes), fanout)``.
    Rows with degree ≤ fanout keep their full (step-invariant) neighborhood;
    rows with degree > fanout are subsampled per step without replacement by
    ranking uniform random keys (smallest ``fanout`` of ``degree`` keys — a
    uniform subset).  ``nodes=None`` means all nodes, with the
    round-invariant precomputation cached on the graph.  The step axis is
    chunked so the key matrix never exceeds ``_MAX_KEY_ELEMS`` elements.
    """
    S = int(num_steps)
    fanout = max(int(fanout), 1)
    if nodes is None:
        plan = _all_nodes_plan(graph, fanout)
    else:
        plan = _build_sampling_plan(graph, nodes, fanout)
    n = plan.num_rows
    table = np.zeros((S, n, fanout), np.int32)
    mask = np.zeros((S, n, fanout), np.float32)
    if n == 0 or S == 0 or graph.num_edges == 0:
        return table, mask
    if plan.keep_idx.size:
        table[:, plan.keep_idx] = plan.keep_table[None]
        mask[:, plan.keep_idx] = plan.keep_mask[None]
    if plan.over_idx.size:
        o_idx, dmax = plan.over_idx, plan.over_dmax
        per_chunk = max(1, _MAX_KEY_ELEMS // max(o_idx.size * dmax, 1))
        for s0 in range(0, S, per_chunk):
            s1 = min(S, s0 + per_chunk)
            keys = rng.random((s1 - s0, o_idx.size, dmax))
            keys[:, plan.over_invalid] = np.inf
            sel = np.argpartition(keys, fanout - 1, axis=-1)[..., :fanout]
            table[s0:s1, o_idx] = graph.indices[
                plan.over_starts[None, :, None] + sel]
        mask[:, o_idx] = 1.0
    return table, mask


def sample_neighbors(graph: CSRGraph, nodes: np.ndarray, fanout: int,
                     rng: np.random.Generator, rng_compat: bool = False
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Uniformly sample up to ``fanout`` neighbors per node.

    Returns ``(table, mask)`` of shape ``(len(nodes), fanout)``.  Nodes with
    degree ≤ fanout keep all neighbors (mask marks the real ones), matching
    full-neighbor aggregation in the limit fanout → max_deg (σ²_bias → 0).
    ``rng_compat=True`` replays the original per-node ``rng.choice`` stream
    (see module docstring); the default is the vectorized path.
    """
    if rng_compat:
        return _sample_neighbors_loop(graph, nodes, fanout, rng)
    table, mask = sample_neighbors_batched(graph, nodes, fanout, rng,
                                           num_steps=1)
    return table[0], mask[0]


def sample_minibatch(train_nodes: np.ndarray, batch_size: int,
                     rng: np.random.Generator) -> np.ndarray:
    """i.i.d. mini-batch ξ of size B (Eq. 2/4)."""
    replace = batch_size > train_nodes.size
    return rng.choice(train_nodes, size=batch_size, replace=replace)


def sample_minibatch_batched(train_nodes: np.ndarray, batch_size: int,
                             num_steps: int, rng: np.random.Generator
                             ) -> np.ndarray:
    """``num_steps`` stacked mini-batches ``(num_steps, batch_size)``.

    Without replacement within a step when the pool allows it (random-keys
    ranking, one draw for the whole stack), with replacement otherwise —
    the same per-step semantics as :func:`sample_minibatch`.
    """
    tn = np.asarray(train_nodes)
    if batch_size > tn.size:
        return tn[rng.integers(0, tn.size, size=(num_steps, batch_size))]
    keys = rng.random((num_steps, tn.size))
    if batch_size == tn.size:
        idx = np.argsort(keys, axis=1)
    else:
        idx = np.argpartition(keys, batch_size - 1, axis=1)[:, :batch_size]
    return tn[idx]


def sample_round_batched(graph: CSRGraph, num_steps: int, fanout: int,
                         rng: np.random.Generator,
                         n_pad: Optional[int] = None,
                         fanout_pad: Optional[int] = None,
                         rng_compat: bool = False
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """All of one round's neighbor tables for one graph, stacked on a K axis.

    Returns ``(tables, masks)`` of shape ``(num_steps, n_pad, fanout_pad)``
    — the per-machine slab of the engine's ``(P, K, …)`` round inputs
    (:mod:`repro.core.engine`).  The default path is one vectorized draw for
    the whole round; with ``rng_compat=True`` draws are made step-by-step
    from ``rng`` in the same order as ``num_steps`` sequential
    :func:`sample_neighbors` calls, so pre-refactor RNG streams are
    reproduced exactly.
    """
    n = graph.num_nodes
    n_pad = n if n_pad is None else n_pad
    fanout_pad = fanout if fanout_pad is None else fanout_pad
    tables = np.zeros((num_steps, n_pad, fanout_pad), np.int32)
    masks = np.zeros((num_steps, n_pad, fanout_pad), np.float32)
    nodes = np.arange(n)
    w = min(fanout, fanout_pad)
    if rng_compat:
        for k in range(num_steps):
            t, m = _sample_neighbors_loop(graph, nodes, fanout, rng)
            tables[k, :n, :w] = t[:, :w]
            masks[k, :n, :w] = m[:, :w]
    else:
        t, m = sample_neighbors_batched(graph, None, fanout, rng,
                                        num_steps=num_steps)
        tables[:, :n, :w] = t[..., :w]
        masks[:, :n, :w] = m[..., :w]
    return tables, masks


def sample_serving_tables(graphs, fanout: int, rng: np.random.Generator,
                          n_pad: int) -> Tuple[np.ndarray, np.ndarray]:
    """One serving wave's neighbor tables for P per-machine (extended) graphs.

    The inference-time entry point used by the GNN serving backend
    (:mod:`repro.serving.gnn`): returns ``(tables, masks)`` stacked
    ``(P, n_pad, fanout)`` — one fixed-shape table per machine over ALL of
    its extended-graph rows, drawn through the vectorized
    :func:`sample_neighbors_batched` path (the cached all-nodes sampling
    plan makes repeated waves cheap).  ``fanout ≥ max degree`` degenerates
    to the full-neighbor table, which is what makes fanout the serving
    accuracy/latency knob: full width reproduces the single-machine forward
    exactly, narrower widths trade σ²_bias for smaller tables.
    """
    P = len(graphs)
    fanout = max(int(fanout), 1)
    tables = np.zeros((P, n_pad, fanout), np.int32)
    masks = np.zeros((P, n_pad, fanout), np.float32)
    for p, g in enumerate(graphs):
        if g.num_nodes > n_pad:
            raise ValueError(f"graph {p} has {g.num_nodes} rows > n_pad "
                             f"{n_pad}")
        t, m = sample_neighbors_batched(g, None, fanout, rng, num_steps=1)
        tables[p, : g.num_nodes] = t[0]
        masks[p, : g.num_nodes] = m[0]
    return tables, masks


@dataclasses.dataclass
class NeighborSampler:
    """Stateful sampler bound to one (sub)graph.

    ``fanout_ratio`` optionally expresses fanout as a fraction of max degree —
    the knob swept in the paper's Figure 6 ("effect of sampling on local
    machine").  ``fanout=None`` + ``ratio=None`` means full neighbors.
    ``rng_compat`` selects the legacy per-node draw stream (module docstring).
    """

    graph: CSRGraph
    fanout: Optional[int] = 10
    fanout_ratio: Optional[float] = None
    seed: int = 0
    rng_compat: bool = False

    def __post_init__(self):
        self._rng = np.random.default_rng(self.seed)
        if self.fanout_ratio is not None:
            md = max(self.graph.max_degree(), 1)
            self.fanout = max(1, int(round(self.fanout_ratio * md)))
        if self.fanout is None:
            self.fanout = max(self.graph.max_degree(), 1)

    def minibatch(self, train_nodes: np.ndarray, batch_size: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(batch_nodes, neighbor_table, mask) — one step's ξ with Ñ(v)."""
        batch = sample_minibatch(train_nodes, batch_size, self._rng)
        table, mask = sample_neighbors(self.graph, batch, self.fanout,
                                       self._rng, rng_compat=self.rng_compat)
        return batch.astype(np.int32), table, mask

    def full_neighbor_batch(self, train_nodes: np.ndarray, batch_size: int
                            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Correction-step batch: uniform ξ with FULL neighbors (Eq. 2)."""
        batch = sample_minibatch(train_nodes, batch_size, self._rng)
        md = max(self.graph.max_degree(), 1)
        table, mask = gather_neighbor_rows(self.graph, batch, md)
        return batch.astype(np.int32), table, mask


# --------------------------------------------------------------------------
# Device-resident sampling (module docstring, "Device-resident sampling")
# --------------------------------------------------------------------------
#: Widths up to this use the pairwise-rank without-replacement selection
#: (O(dmax²) compares, fuses well); wider rows fall back to ``lax.top_k``.
_RANK_SELECT_MAX_WIDTH = 128


@dataclasses.dataclass(frozen=True)
class DeviceCSR:
    """P CSR shards as padded per-row neighbor tables + train pools,
    resident on the accelerator.

    One instance is built per ``(round kind, fanout)`` by
    :func:`build_device_csr` and reused every round — the device-side
    analogue of the host path's cached :class:`_SamplingPlan`.  All arrays
    are stacked on a leading machine axis so the samplers vmap over it (or
    shard it over a ``('machine',)`` mesh).
    """

    nbr: Any            # (P, n_pad, dmax) int32 — row's neighbors, 0 past
    #                     its degree
    degrees: Any        # (P, n_pad) int32 — 0 on padded rows
    train_nodes: Any    # (P, t_pad) int32 — per-machine train pools
    train_counts: Any   # (P,) int32
    fanouts: Any        # (P,) int32 — per-machine effective fanout
    dmax: int           # max degree over all shards (static key width)

    @property
    def num_machines(self) -> int:
        return int(self.degrees.shape[0])

    @property
    def n_pad(self) -> int:
        return int(self.degrees.shape[1])

    @property
    def select(self) -> str:
        """How the sampled ids are read out of ``nbr``: ``"row_table"``
        (picked by the pairwise-rank one-hot, no gather) or ``"row_gather"``
        (``top_k`` slots gathered, ``dmax > _RANK_SELECT_MAX_WIDTH``)."""
        return ("row_table" if self.dmax <= _RANK_SELECT_MAX_WIDTH
                else "row_gather")


# a pytree (dmax is static metadata), so a DeviceCSR passes straight
# through jit/vmap boundaries
jax.tree_util.register_dataclass(
    DeviceCSR,
    data_fields=["nbr", "degrees", "train_nodes", "train_counts",
                 "fanouts"],
    meta_fields=["dmax"])


def build_device_csr(graphs: Sequence[CSRGraph], n_pad: Optional[int] = None,
                     train_nodes: Optional[Sequence[np.ndarray]] = None,
                     fanouts: Optional[Sequence[int]] = None,
                     t_pad_min: int = 1, sharding=None) -> DeviceCSR:
    """Stack P CSR shards into one device-resident :class:`DeviceCSR`.

    ``train_nodes`` may be omitted for table-only use (serving);
    ``fanouts`` defaults to full width (callers pass the per-machine
    resolved fanouts of the fanout_ratio knob).  ``t_pad_min`` floors the
    train-pool padding so fixed-size batches can always be gathered.
    ``sharding`` (a ``NamedSharding`` over the machine axis) places the
    stacks shard-per-device for the shard_map backend.
    """
    P = len(graphs)
    if P == 0:
        raise ValueError("build_device_csr needs at least one graph")
    n_pad = max(g.num_nodes for g in graphs) if n_pad is None else int(n_pad)
    pools = ([np.zeros(0, np.int64)] * P if train_nodes is None
             else [np.asarray(t) for t in train_nodes])
    t_pad = max(max(p.size for p in pools), int(t_pad_min), 1)
    dmax = max(max(g.max_degree() for g in graphs), 1)
    fo = ([dmax] * P if fanouts is None else [int(f) for f in fanouts])

    nbr = np.zeros((P, n_pad, dmax), np.int32)
    degrees = np.zeros((P, n_pad), np.int32)
    tn = np.zeros((P, t_pad), np.int32)
    tc = np.zeros((P,), np.int32)
    for p, g in enumerate(graphs):
        if g.num_nodes > n_pad:
            raise ValueError(f"graph {p} has {g.num_nodes} rows > n_pad "
                             f"{n_pad}")
        deg = np.diff(g.indptr)
        degrees[p, : g.num_nodes] = deg
        # edge e of row v sits in slot e - indptr[v]
        rows = np.repeat(np.arange(g.num_nodes), deg)
        slots = np.arange(g.num_edges) - np.repeat(g.indptr[:-1], deg)
        nbr[p, rows, slots] = g.indices
        tn[p, : pools[p].size] = pools[p]
        tc[p] = pools[p].size

    put = ((lambda x: jax.device_put(jnp.asarray(x), sharding))
           if sharding is not None else jnp.asarray)
    return DeviceCSR(nbr=put(nbr), degrees=put(degrees), train_nodes=put(tn),
                     train_counts=put(tc),
                     fanouts=put(np.asarray(fo, np.int32)), dmax=dmax)


def _rank_hits(bits, valid, width: int):
    """One-hot ``(…, dmax, min(width, dmax))``: slot ``s`` holds the
    ``j``-th smallest key of its row — the pairwise-rank path of
    :func:`_rank_select` (``dmax <= _RANK_SELECT_MAX_WIDTH``)."""
    dmax = bits.shape[-1]
    w = min(width, dmax)
    # pack the slot index into the low bits: one `>` compare then gives
    # a strict total order (random key bits break first, index second),
    # and invalid slots get the top bit so valid < invalid always
    ib = max(int(dmax - 1).bit_length(), 1)
    ia = jnp.arange(dmax, dtype=jnp.uint32)
    keys = jnp.where(
        valid,
        ((bits >> jnp.uint32(1 + ib)) << jnp.uint32(ib)) | ia,
        (jnp.uint32(1) << jnp.uint32(31)) | ia)
    gt = keys[..., :, None] > keys[..., None, :]
    rank = jnp.sum(gt, axis=-1, dtype=jnp.int32)                # (…, dmax)
    slot = jnp.where(rank < w, rank, w)
    return slot[..., :, None] == jnp.arange(w, dtype=jnp.int32)


def _pick(hit, values, width: int):
    """``values (…, dmax)`` at the slots ``hit`` selects, ``(…, width)``,
    zero past ``min(width, dmax)``: a masked sum, no gather."""
    return _pad_to(jnp.sum(jnp.where(hit, values[..., :, None], 0),
                           axis=-2), width)


def _pad_to(x, width: int):
    if x.shape[-1] == width:
        return x
    pad = jnp.zeros(x.shape[:-1] + (width - x.shape[-1],), x.dtype)
    return jnp.concatenate([x, pad], axis=-1)


def _rank_select(bits, valid, width: int):
    """Indices of the ``width`` smallest keys per row, without replacement.

    ``bits (…, dmax) uint32`` are i.i.d. random keys; ``valid`` marks real
    slots.  Valid keys are halved (low bit dropped) and invalid slots set to
    the odd maximum, so valid < invalid strictly and an index tie-break
    makes the order total — the selected set is a uniform without-
    replacement subset of the valid slots (random-keys ranking, exactly the
    host path's argument).  Implemented as pairwise-rank + compaction
    because XLA's ``top_k``/``sort`` are far slower on CPU at these widths.
    """
    dmax = bits.shape[-1]
    if dmax <= _RANK_SELECT_MAX_WIDTH:
        return _pick(_rank_hits(bits, valid, width),
                     jnp.arange(dmax, dtype=jnp.int32), width)
    keys = jnp.where(valid, bits >> jnp.uint32(1), jnp.uint32(0xFFFFFFFF))
    # top_k takes the LARGEST, so rank complemented keys; XLA's top_k is
    # stable, which reproduces the same lowest-index tie-break
    _, sel = jax.lax.top_k(keys ^ jnp.uint32(0xFFFFFFFF), min(width, dmax))
    return _pad_to(sel.astype(jnp.int32), width)


def _neighbor_tables_step(step_key, nbr_p, degrees_p, fanout_p, width: int,
                          dmax: int):
    """One machine-step's ``(n_pad, width)`` table + mask (pure jax)."""
    n_pad = degrees_p.shape[0]
    bits = jax.random.bits(jax.random.fold_in(step_key, 0), (n_pad, dmax),
                           dtype=jnp.uint32)
    col = jnp.arange(dmax, dtype=jnp.int32)
    valid_key = col[None, :] < degrees_p[:, None]
    eff = jnp.minimum(degrees_p, fanout_p)
    valid = jnp.arange(width, dtype=jnp.int32)[None, :] < eff[:, None]
    if dmax <= _RANK_SELECT_MAX_WIDTH:
        # the one-hot that ranks the slots picks their ids too: no gather
        picked = _pick(_rank_hits(bits, valid_key, width), nbr_p, width)
    else:
        picked = jnp.take_along_axis(
            nbr_p, _rank_select(bits, valid_key, width), axis=-1)
    table = jnp.where(valid, picked, 0).astype(jnp.int32)
    return table, valid.astype(jnp.float32)


def _minibatch_step(step_key, train_p, count_p, batch_size: int):
    """One machine-step's ``(B,)`` train batch: WOR when the pool allows it,
    with replacement otherwise — :func:`sample_minibatch` semantics."""
    t_pad = train_p.shape[0]
    bits = jax.random.bits(jax.random.fold_in(step_key, 1), (t_pad,),
                           dtype=jnp.uint32)
    valid = jnp.arange(t_pad, dtype=jnp.int32) < count_p
    wor = _rank_select(bits, valid, batch_size)
    rep = jax.random.randint(jax.random.fold_in(step_key, 2), (batch_size,),
                             0, jnp.maximum(count_p, 1))
    sel = jnp.where(count_p >= batch_size, wor[:batch_size], rep)
    return train_p[sel].astype(jnp.int32)


def sample_round_device(dcsr: DeviceCSR, key, num_steps: int, width: int,
                        batch_size: int):
    """One round's sampled inputs, drawn entirely on device.

    Returns ``(tables, masks, batches, bmasks)`` shaped exactly like the
    host path's :func:`repro.data.graph_loader.sample_round` stacks —
    ``(P, K, n_pad, width)`` / ``(P, K, B)`` — but as device arrays from the
    documented ``jax.random`` stream (module docstring), so the call is one
    asynchronous dispatch the engine can overlap with the previous round's
    compute.  ``key`` is the per-round key (caller folds the round index);
    per-machine fanouts narrower than ``width`` (the fanout_ratio knob)
    are masked per row via ``dcsr.fanouts``.
    """
    dmax = dcsr.dmax

    def one_machine(p, nbr_p, degrees_p, train_p, count_p, fanout_p):
        kp = jax.random.fold_in(key, p)

        def one_step(s):
            ks = jax.random.fold_in(kp, s)
            table, mask = _neighbor_tables_step(
                ks, nbr_p, degrees_p, fanout_p, width, dmax)
            batch = _minibatch_step(ks, train_p, count_p, batch_size)
            return table, mask, batch

        return jax.vmap(one_step)(jnp.arange(num_steps))

    P = dcsr.num_machines
    tables, masks, batches = jax.vmap(one_machine)(
        jnp.arange(P), dcsr.nbr, dcsr.degrees, dcsr.train_nodes,
        dcsr.train_counts, dcsr.fanouts)
    bmasks = jnp.ones((P, num_steps, batch_size), jnp.float32)
    return tables, masks, batches, bmasks


def sample_serving_tables_device(dcsr: DeviceCSR, key, width: int):
    """Device-side :func:`sample_serving_tables`: one wave's ``(P, n_pad,
    width)`` tables + masks over P extended graphs, from ``fold_in(key, p)``
    per machine (step index 0) — no host loop between serving waves."""
    dmax = dcsr.dmax

    def one_machine(p, nbr_p, degrees_p):
        ks = jax.random.fold_in(jax.random.fold_in(key, p), 0)
        return _neighbor_tables_step(ks, nbr_p, degrees_p, jnp.int32(width),
                                     width, dmax)

    return jax.vmap(one_machine)(jnp.arange(dcsr.num_machines), dcsr.nbr,
                                 dcsr.degrees)
