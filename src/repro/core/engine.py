"""Unified vectorized LLCG round engine.

The paper's Algorithms 1/2 are a *round program*: K dependency-free local
steps on P machines, one model-average collective, S server-correction
steps.  This module compiles that whole round into ONE jit'd function —
``jax.lax.scan`` across the K step axis, a machine axis executed by a
pluggable backend — so a round costs a single dispatch instead of P×K
host round-trips:

* ``backend="vmap"``       — simulation on any host: the machine axis is a
  ``jax.vmap`` batch dimension, averaging is a mean over it.
* ``backend="shard_map"``  — one device per machine on a ``('machine',)``
  mesh: the local phase runs device-local, averaging is one
  ``jax.lax.pmean`` (byte-exactly the paper's communication).

Both backends execute the SAME per-machine round body
(:func:`repro.core.machine.make_local_round`), so they agree numerically
and are differential-tested against each other (``tests/test_engine.py``).

Three round modes cover every strategy in the paper:

* ``mode="local"`` — Alg. 1/2: K independent local steps per machine, then
  parameter averaging (+ optional S corrections).  PSGD-PA, LLCG, and the
  single-machine reference (P=1) are all configs over this mode.
* ``mode="sync"``  — fully-synchronous baseline: every step averages
  gradients across machines before a single shared update, on
  host-materialized inputs.
* ``mode="halo"``  — the GGS baseline with its defining cost EXECUTED: each
  scan step first runs the cut-node feature exchange described by a
  :class:`repro.graph.halo.HaloProgram` (owner-bucketed send slots, padded
  to the mesh-wide max, so it lowers to one fixed-shape
  ``jax.lax.all_gather`` over the ``('machine',)`` axis), splices the
  received halo rows into the extended feature buffer
  (:func:`repro.core.machine.halo_fill`), then does the sync-mode
  per-step gradient averaging.  The ``vmap`` backend simulates the
  collective with the same padded gathers, so both backends stay
  differential-testable; ``History`` bytes for this mode come from the
  executed collective's operand shapes
  (:meth:`~repro.graph.halo.HaloProgram.exchange_bytes`), not host-side
  accounting.

Communication/steps accounting and the :class:`History` container live
here too, so every strategy reports bytes/steps identically.

**K-bucketing.**  The scan length K is a static shape, so a ρ>1
``local_epoch_schedule`` would retrace the round program once per distinct
K.  Passing a :class:`repro.core.schedules.KBucketing` policy to
:func:`run_schedule` rounds each scheduled K up to a geometric grid of
bucket lengths (``min_len · growth^i``); the padded tail executes as
*masked* steps — a per-step validity flag ``step_valid`` threaded through
every round body gates the optimizer via
:func:`repro.optim.optimizers.masked_update`, so a masked step changes
neither params, step count nor moments and the bucketed run matches the
unbucketed one bit-for-bit while compiling only O(#buckets) programs
(:attr:`RoundProgram.num_retraces` counts them).  Byte/step accounting
always uses the *real* K.

Host-side round inputs come from the vectorized sampler
(:mod:`repro.graph.sampling`); its ``rng_compat=True`` knob replays the
legacy per-node draw stream so engine trajectories can be compared
bit-for-bit against pre-vectorization references.

**Tracing.**  The local phase and the correction compile as two programs,
``jit_counted_round*`` and ``jit_counted_correction*`` in a profiler trace,
with the named scopes ``local_steps``, ``averaging`` and
``correction_step`` in their op metadata.  :func:`run_schedule` marks its
host work with :func:`span`: per round an ``llcg.round`` step span holding
``llcg.sample``, ``llcg.dispatch``, ``llcg.read`` (each blocking read),
``llcg.evaluate`` and ``llcg.checkpoint``.
"""
from __future__ import annotations

import dataclasses
import inspect
import json
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import TraceCounter, trace_signature
from repro.comm.compress import (check_compression, compress_features,
                                 compress_tree, decompress_features,
                                 decompress_tree, machine_keys)
from repro.core.machine import halo_fill, make_local_round, make_loss_fn
from repro.core.schedules import KBucketing
from repro.optim.optimizers import Optimizer, apply_updates, masked_update


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``llcg.<name>`` on the profiler's clock, beside the
    device's events.  Without a running trace it records nothing."""
    return jax.profiler.TraceAnnotation("llcg." + name)


# --------------------------------------------------------------------------
# History — the quantities plotted in the paper (Fig. 4, Table 1)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class History:
    strategy: str
    rounds: List[int] = dataclasses.field(default_factory=list)
    steps_cum: List[int] = dataclasses.field(default_factory=list)
    val_score: List[float] = dataclasses.field(default_factory=list)
    train_loss: List[float] = dataclasses.field(default_factory=list)
    bytes_cum: List[float] = dataclasses.field(default_factory=list)
    meta: Dict = dataclasses.field(default_factory=dict)

    @property
    def final_score(self) -> float:
        return self.val_score[-1] if self.val_score else float("nan")

    def avg_mb_per_round(self) -> float:
        if not self.bytes_cum:
            return 0.0
        return self.bytes_cum[-1] / max(len(self.rounds), 1) / 1e6

    def to_json(self) -> Dict:
        """JSON-able snapshot for checkpoint manifests.

        Non-serializable ``meta`` entries are dropped (they are
        reconstructed by the resuming trainer), and so is the wall-clock
        ``round_seconds`` series, which is no training state and would make
        identical runs write different manifests; the per-round series are
        kept verbatim — JSON round-trips Python floats exactly, which is
        what keeps ``bytes_cum`` accumulation bit-identical across resume.
        Every value is a copy: an asynchronous checkpoint writer serializes
        the snapshot after later rounds have appended to ``meta``'s lists.
        """
        meta = {}
        for k, v in self.meta.items():
            if k == "round_seconds":
                continue
            try:
                meta[k] = json.loads(json.dumps(v))
            except (TypeError, ValueError):
                continue
        return {"strategy": self.strategy, "rounds": list(self.rounds),
                "steps_cum": list(self.steps_cum),
                "val_score": list(self.val_score),
                "train_loss": list(self.train_loss),
                "bytes_cum": list(self.bytes_cum), "meta": meta}

    @classmethod
    def from_json(cls, d: Dict) -> "History":
        return cls(strategy=d["strategy"], rounds=list(d["rounds"]),
                   steps_cum=list(d["steps_cum"]),
                   val_score=list(d["val_score"]),
                   train_loss=list(d["train_loss"]),
                   bytes_cum=list(d["bytes_cum"]), meta=dict(d["meta"]))


# --------------------------------------------------------------------------
# Engine config / per-round inputs / carried state
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_machines: int
    mode: str = "local"            # "local" (Alg. 1/2) | "sync" | "halo" (GGS)
    backend: str = "vmap"          # "vmap" | "shard_map"
    with_correction: bool = False  # Alg. 2 lines 13-18
    reset_local_opt: bool = True   # fresh local optimizer each round (line 3)
    # payload codecs (repro.comm.compress): `compression` applies to the
    # averaging collective of mode="local" (param deltas on the wire;
    # int8/int8_ef use stochastic rounding, int8_ef carries the per-machine
    # error-feedback residual in EngineState.comm_residual);
    # `halo_compression` applies to the cut-node feature all_gather of
    # mode="halo".  Each is ignored by the modes it doesn't name, and
    # "none" leaves the pre-compression code path bit-identical.
    compression: str = "none"
    halo_compression: str = "none"
    comm_seed: int = 0             # base of the stochastic-rounding key fold


@dataclasses.dataclass
class RoundInputs:
    """One round's host-sampled data, stacked ``(P, K, …)``.

    ``corr_tables`` is either the static full-neighbor table ``(N, F)`` or,
    for the sampling-at-correction ablation, per-step tables ``(S, N, F)``.
    ``step_valid`` is the K-bucketing validity flag (1.0 real / 0.0 padded
    step); ``None`` means every step is real.

    The four ``halo_*`` tables are the :class:`repro.graph.halo.HaloProgram`
    index arrays driving ``mode="halo"``; the engine's feature buffer then
    carries only local rows and the exchange fills the halo rows on device
    every step.  They are required for that mode and ignored otherwise.
    """

    tables: Any                    # (P, K, n_max, F) int32
    masks: Any                     # (P, K, n_max, F) f32
    batches: Any                   # (P, K, B) int32
    bmasks: Any                    # (P, K, B) f32
    step_valid: Any = None         # (K,) f32 — 0.0 marks masked padding
    corr_feats: Any = None         # (N, d) full-graph features
    corr_labels: Any = None        # (N,)
    corr_tables: Any = None        # (N, F) or (S, N, F)
    corr_masks: Any = None
    corr_batches: Any = None       # (S, B_S) int32
    corr_bmasks: Any = None        # (S, B_S) f32
    corr_agg: Any = None           # AggOperands for the correction forward
                                   # (None → padded tables, bit-identical)
    halo_send_idx: Any = None      # (P, max_send) int32
    halo_recv_idx: Any = None      # (P, max_halo) int32
    halo_dest_idx: Any = None      # (P, max_halo) int32
    halo_recv_valid: Any = None    # (P, max_halo) f32


@dataclasses.dataclass
class EngineState:
    params: Any
    # sync mode / persistent local opt: the optimizer state (stacked (P, …)
    # in local mode); with reset_local_opt a scalar placeholder, since the
    # per-round state is rebuilt from the incoming params inside the round
    local_opt_state: Any
    server_opt_state: Any = None
    # compression="int8_ef": per-machine error-feedback residual, a params
    # pytree stacked (P, …) — the quantization error each machine adds back
    # into its next round's delta.  None for every other codec.
    comm_residual: Any = None


# --------------------------------------------------------------------------
# RoundProgram — one compiled round, two backends
# --------------------------------------------------------------------------
class RoundProgram:
    """The LLCG round as a single compiled program.

    ``run_round`` executes the local phase + averaging (+ corrections) in
    at most two dispatches.  Rounds with different (bucketed) K retrace
    once per distinct scan length — the static shape — which
    :attr:`num_retraces` counts and a :class:`~repro.core.schedules.
    KBucketing` policy in :func:`run_schedule` bounds to O(#buckets) for
    the ρ>1 schedule.
    """

    def __init__(self, model, local_opt: Optimizer,
                 server_opt: Optional[Optimizer], cfg: EngineConfig,
                 mesh=None):
        if cfg.mode not in ("local", "sync", "halo"):
            raise ValueError(f"unknown mode {cfg.mode!r}")
        if cfg.backend not in ("vmap", "shard_map"):
            raise ValueError(f"unknown backend {cfg.backend!r}")
        if cfg.backend == "shard_map" and mesh is None:
            raise ValueError("backend='shard_map' requires a mesh with a "
                             "'machine' axis")
        if cfg.with_correction and server_opt is None:
            raise ValueError("with_correction requires a server optimizer")
        check_compression(cfg.compression)
        check_compression(cfg.halo_compression, halo=True)
        self.model, self.cfg, self.mesh = model, cfg, mesh
        self.local_opt, self.server_opt = local_opt, server_opt
        # distinct round/correction programs compiled over the RUN (not the
        # process): signature-aware counters, so a resumed process does not
        # re-count shapes the pre-crash process already compiled
        self._round_traces = TraceCounter()
        self._corr_traces = TraceCounter()
        self._grad_fn = jax.value_and_grad(make_loss_fn(model))
        # stochastic-rounding key stream: comm_seed → per-run_round-call
        # fold (reset by init_state, so runs are reproducible) → per-machine
        # fold inside the round
        self._comm_stochastic = (cfg.mode == "local"
                                 and cfg.compression in ("int8", "int8_ef"))
        self._comm_key = jax.random.PRNGKey(cfg.comm_seed)
        self._comm_calls = 0
        self._build_round()
        if cfg.with_correction:
            self._build_correction()

    @property
    def num_retraces(self) -> int:
        return self._round_traces.count_value

    @property
    def num_corr_retraces(self) -> int:
        return self._corr_traces.count_value

    def trace_state(self) -> Dict:
        """JSON-able retrace/key-stream position (for exact resume)."""
        return {"round": self._round_traces.snapshot(),
                "corr": self._corr_traces.snapshot(),
                "comm_calls": self._comm_calls}

    def restore_trace_state(self, snap: Dict) -> None:
        self._round_traces.restore(snap["round"])
        self._corr_traces.restore(snap["corr"])
        self._comm_calls = int(snap["comm_calls"])

    def _jit_counting(self, fn):
        """jit ``fn``, incrementing :attr:`num_retraces` at each trace.

        The increment is a Python side effect inside the traced function, so
        it fires exactly once per XLA compilation (new static shapes — e.g.
        a new scan length K) and never on cached dispatches.  Counting goes
        through the trace *signature* so a resumed process re-compiling a
        shape the pre-crash process already traced does not inflate the
        run's retrace count.  The compiled module is named
        ``jit_counted_round``.
        """
        def counted_round(*args):
            self._round_traces.count(trace_signature(args))
            return fn(*args)
        return jax.jit(counted_round)

    # ----------------------------------------------------------- local phase
    def _build_round(self):
        cfg = self.cfg
        local_round = make_local_round(self.model, self.local_opt,
                                       reset_opt=cfg.reset_local_opt)
        grad_fn = self._grad_fn

        def masked_mean(losses, svalid):
            """Mean of per-step losses over REAL steps only (masked padding
            contributes 0 to the numerator and denominator)."""
            per_step = losses.size // svalid.size  # machines sharing a step
            return jnp.sum(losses) / jnp.clip(
                jnp.sum(svalid) * per_step, 1.0, None)

        comp = cfg.compression if cfg.mode == "local" else "none"
        stoch = comp in ("int8", "int8_ef")
        ef = comp == "int8_ef"
        halo_comp = cfg.halo_compression if cfg.mode == "halo" else "none"

        def _local_steps(params, opt_state, feats, labels, tables, masks,
                         batches, bmasks, svalid):
            """The K local steps per machine (vmap over P) — shared by the
            plain and the compressed averaging paths."""
            with jax.named_scope("local_steps"):
                if cfg.reset_local_opt:
                    # fresh per-round optimizer (Alg. 2 line 3): the carried
                    # opt_state is a scalar placeholder, threaded through
                    # unchanged so the round signature stays uniform
                    run = lambda f, l, t, m, b, bm: local_round(
                        params, None, f, l, t, m, b, bm, svalid)
                    p_new, _, losses = jax.vmap(run)(feats, labels, tables,
                                                     masks, batches, bmasks)
                    o_new = opt_state
                else:
                    p_new, o_new, losses = jax.vmap(
                        local_round,
                        in_axes=(None, 0, 0, 0, 0, 0, 0, 0, None))(
                        params, opt_state, feats, labels, tables, masks,
                        batches, bmasks, svalid)
            return p_new, o_new, losses

        def round_local(params, opt_state, feats, labels, tables, masks,
                        batches, bmasks, svalid):
            """K local steps per machine (vmap over P), then averaging."""
            p_new, o_new, losses = _local_steps(
                params, opt_state, feats, labels, tables, masks, batches,
                bmasks, svalid)
            # Alg. 1/2 line 12 — THE inter-machine collective
            with jax.named_scope("averaging"):
                avg = jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0),
                                             p_new)
            return avg, o_new, masked_mean(losses, svalid)

        def round_local_comp(params, opt_state, feats, labels, tables, masks,
                             batches, bmasks, svalid, *extra):
            """Compressed averaging: each machine quantizes its param DELTA
            (new params − round input), the average is taken over the
            dequantized deltas — exactly what the all_gather of compressed
            payloads hands every machine — and with error feedback the
            quantization error stays on the machine and is added back into
            the next round's delta (EngineState.comm_residual)."""
            p_new, o_new, losses = _local_steps(
                params, opt_state, feats, labels, tables, masks, batches,
                bmasks, svalid)
            if ef:
                comm_key, residual = extra
            else:
                comm_key = extra[0] if stoch else None
                residual = None
            delta = jax.tree_util.tree_map(lambda a, b: a - b, p_new, params)
            if ef:
                delta = jax.tree_util.tree_map(jnp.add, delta, residual)
            keys = (machine_keys(comm_key, cfg.num_machines) if stoch
                    else None)
            with jax.named_scope("averaging"):
                payload, scales = compress_tree(delta, comp, key=keys,
                                                stacked=True)
                deq = decompress_tree(payload, scales, comp)
                avg = jax.tree_util.tree_map(
                    lambda p0, d: p0 + jnp.mean(d, axis=0), params, deq)
            outs = (avg, o_new, masked_mean(losses, svalid))
            if ef:
                outs += (jax.tree_util.tree_map(jnp.subtract, delta, deq),)
            return outs

        def round_sync(params, opt_state, feats, labels, tables, masks,
                       batches, bmasks, svalid):
            """Per-step gradient averaging across machines (GGS/sync)."""
            xs = jax.tree_util.tree_map(lambda x: jnp.swapaxes(x, 0, 1),
                                        (tables, masks, batches, bmasks))

            def one(carry, step_xs):
                p, o = carry
                table, mask, batch, bmask, valid = step_xs   # each (P, …)
                losses, grads = jax.vmap(
                    grad_fn, in_axes=(None, 0, 0, 0, 0, 0, 0))(
                    p, feats, table, mask, batch, labels, bmask)
                g = jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0),
                                           grads)
                upd, o = masked_update(self.local_opt, g, o, p, valid)
                return (apply_updates(p, upd), o), jnp.mean(losses) * valid

            (params, opt_state), losses = jax.lax.scan(
                one, (params, opt_state), xs + (svalid,))
            return params, opt_state, masked_mean(losses, svalid)

        def round_halo(params, opt_state, feats, labels, tables, masks,
                       batches, bmasks, svalid, send_idx, recv_idx,
                       dest_idx, recv_valid):
            """GGS with the cut-node exchange executed: each step assembles
            the all-gather buffer from every machine's owner-bucketed send
            slots (the vmap simulation of the shard_map collective), fills
            the halo rows, then does the sync-mode gradient averaging."""
            xs = jax.tree_util.tree_map(lambda x: jnp.swapaxes(x, 0, 1),
                                        (tables, masks, batches, bmasks))
            flat_n = send_idx.shape[0] * send_idx.shape[1]
            if halo_comp != "none":
                # compressed exchange: the send buffer is quantized once
                # (features are static within the round), and what every
                # machine sees is the DEQUANTIZED gather — the same values
                # the shard backend reconstructs after its all_gather of
                # int8/bf16 payloads
                send_c = jax.vmap(lambda f, si: f[si])(feats, send_idx)
                payload, scales = compress_features(
                    send_c.reshape(flat_n, feats.shape[-1]), halo_comp)
                gathered_comp = decompress_features(payload, scales,
                                                    halo_comp)

            def one(carry, step_xs):
                p, o = carry
                table, mask, batch, bmask, valid = step_xs   # each (P, …)
                if halo_comp == "none":
                    # the exchange: what all_gather hands every machine
                    send = jax.vmap(lambda f, si: f[si])(feats, send_idx)
                    gathered = send.reshape(flat_n, feats.shape[-1])
                else:
                    gathered = gathered_comp

                def machine_grads(f, ri, di, rv, t, m, b, lab, bm):
                    return grad_fn(p, halo_fill(f, gathered, ri, di, rv),
                                   t, m, b, lab, bm)

                losses, grads = jax.vmap(machine_grads)(
                    feats, recv_idx, dest_idx, recv_valid, table, mask,
                    batch, labels, bmask)
                g = jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0),
                                           grads)
                upd, o = masked_update(self.local_opt, g, o, p, valid)
                return (apply_updates(p, upd), o), jnp.mean(losses) * valid

            (params, opt_state), losses = jax.lax.scan(
                one, (params, opt_state), xs + (svalid,))
            return params, opt_state, masked_mean(losses, svalid)

        body = {"local": round_local_comp if comp != "none" else round_local,
                "sync": round_sync, "halo": round_halo}[cfg.mode]

        if cfg.backend == "vmap":
            self._round = self._jit_counting(body)
            return

        # shard_map backend: same per-machine body, one device per machine.
        from jax.sharding import PartitionSpec as P

        def masked_mean_1d(losses, svalid):
            """Per-shard variant of ``masked_mean``: losses are (K,), no
            machine axis in the denominator (pmean supplies it)."""
            return jnp.sum(losses) / jnp.clip(jnp.sum(svalid), 1.0, None)

        def _shard_local_steps(params, opt_state, feats, labels, tables,
                               masks, batches, bmasks, svalid):
            """One machine's K local steps (leading P axis of size 1
            stripped) — shared by the plain and compressed averaging."""
            if cfg.reset_local_opt:
                o = None  # local_round re-inits from the incoming params
            else:
                o = jax.tree_util.tree_map(lambda x: x[0], opt_state)
            with jax.named_scope("local_steps"):
                return local_round(
                    params, o, feats[0], labels[0], tables[0], masks[0],
                    batches[0], bmasks[0], svalid)

        def shard_local(params, opt_state, feats, labels, tables, masks,
                        batches, bmasks, svalid):
            """One machine's shard (leading P axis of size 1 stripped)."""
            p_new, o_new, losses = _shard_local_steps(
                params, opt_state, feats, labels, tables, masks, batches,
                bmasks, svalid)
            with jax.named_scope("averaging"):
                p_avg = jax.lax.pmean(p_new, "machine")
            loss = jax.lax.pmean(masked_mean_1d(losses, svalid), "machine")
            if cfg.reset_local_opt:
                o_new = opt_state  # scalar placeholder, unchanged
            else:
                o_new = jax.tree_util.tree_map(lambda x: x[None], o_new)
            return p_avg, o_new, loss

        def shard_local_comp(params, opt_state, feats, labels, tables, masks,
                             batches, bmasks, svalid, *extra):
            """Compressed averaging, one machine's shard: the collective is
            an ``all_gather`` of the COMPRESSED delta payloads (int8/bf16 on
            the wire — what the byte accounting prices), dequantized and
            averaged locally.  Numerically identical to the vmap
            simulation's mean over dequantized deltas."""
            p_new, o_new, losses = _shard_local_steps(
                params, opt_state, feats, labels, tables, masks, batches,
                bmasks, svalid)
            if ef:
                comm_key, residual = extra
                res_m = jax.tree_util.tree_map(lambda x: x[0], residual)
            else:
                comm_key = extra[0] if stoch else None
                res_m = None
            delta = jax.tree_util.tree_map(jnp.subtract, p_new, params)
            if ef:
                delta = jax.tree_util.tree_map(jnp.add, delta, res_m)
            key_m = (jax.random.fold_in(comm_key,
                                        jax.lax.axis_index("machine"))
                     if stoch else None)
            with jax.named_scope("averaging"):
                payload, scales = compress_tree(delta, comp, key=key_m)
                g_payload = jax.lax.all_gather(payload, "machine")
                g_scales = (jax.lax.all_gather(scales, "machine")
                            if scales is not None else None)
                deq_all = decompress_tree(g_payload, g_scales, comp)
                p_avg = jax.tree_util.tree_map(
                    lambda p0, d: p0 + jnp.mean(d, axis=0), params, deq_all)
            loss = jax.lax.pmean(masked_mean_1d(losses, svalid), "machine")
            if cfg.reset_local_opt:
                o_out = opt_state  # scalar placeholder, unchanged
            else:
                o_out = jax.tree_util.tree_map(lambda x: x[None], o_new)
            outs = (p_avg, o_out, loss)
            if ef:
                deq_self = decompress_tree(payload, scales, comp)
                res_new = jax.tree_util.tree_map(jnp.subtract, delta,
                                                 deq_self)
                outs += (jax.tree_util.tree_map(lambda x: x[None], res_new),)
            return outs

        def shard_sync(params, opt_state, feats, labels, tables, masks,
                       batches, bmasks, svalid):
            feats_p, labels_p = feats[0], labels[0]

            def one(carry, step_xs):
                p, o = carry
                table, mask, batch, bmask, valid = step_xs
                loss, grads = grad_fn(p, feats_p, table, mask, batch,
                                      labels_p, bmask)
                grads = jax.lax.pmean(grads, "machine")
                upd, o = masked_update(self.local_opt, grads, o, p, valid)
                return (apply_updates(p, upd), o), jax.lax.pmean(
                    loss, "machine") * valid

            (params, opt_state), losses = jax.lax.scan(
                one, (params, opt_state), (tables[0], masks[0], batches[0],
                                           bmasks[0], svalid))
            return params, opt_state, masked_mean_1d(losses, svalid)

        def shard_halo(params, opt_state, feats, labels, tables, masks,
                       batches, bmasks, svalid, send_idx, recv_idx,
                       dest_idx, recv_valid):
            """One machine's shard of the halo round: a REAL fixed-shape
            ``all_gather`` of the owner-bucketed send buffer each scan step,
            then the sync-mode per-step gradient pmean.  Masked steps
            (``svalid == 0``) skip the optimizer but still execute the
            exchange, so the program stays shape-stable under K-bucketing."""
            feats_p, labels_p = feats[0], labels[0]
            send_i, recv_i = send_idx[0], recv_idx[0]
            dest_i, rvalid = dest_idx[0], recv_valid[0]
            if halo_comp != "none":
                # quantize the send buffer once per round (features are
                # static); the per-step collective then moves int8/bf16
                # payloads — the compressed wire format the accounting and
                # the dryrun HLO cross-check price
                send_payload, send_scales = compress_features(
                    feats_p[send_i], halo_comp)

            def one(carry, step_xs):
                p, o = carry
                table, mask, batch, bmask, valid = step_xs
                if halo_comp == "none":
                    gathered = jax.lax.all_gather(feats_p[send_i], "machine")
                    gflat = gathered.reshape(-1, feats_p.shape[-1])
                else:
                    g_p = jax.lax.all_gather(send_payload, "machine")
                    g_s = (jax.lax.all_gather(send_scales, "machine")
                           if send_scales is not None else None)
                    gflat = decompress_features(g_p, g_s, halo_comp).reshape(
                        -1, feats_p.shape[-1])
                ext = halo_fill(feats_p, gflat, recv_i, dest_i, rvalid)
                loss, grads = grad_fn(p, ext, table, mask, batch, labels_p,
                                      bmask)
                grads = jax.lax.pmean(grads, "machine")
                upd, o = masked_update(self.local_opt, grads, o, p, valid)
                return (apply_updates(p, upd), o), jax.lax.pmean(
                    loss, "machine") * valid

            (params, opt_state), losses = jax.lax.scan(
                one, (params, opt_state), (tables[0], masks[0], batches[0],
                                           bmasks[0], svalid))
            return params, opt_state, masked_mean_1d(losses, svalid)

        pspec = P("machine")
        if cfg.mode == "local":
            ospec = P() if cfg.reset_local_opt else pspec
            in_specs = (P(), ospec, pspec, pspec, pspec, pspec, pspec, pspec,
                        P())
            out_specs = (P(), ospec, P())
            shard_body = shard_local
            if comp != "none":
                shard_body = shard_local_comp
                if stoch:
                    in_specs += (P(),)        # replicated comm key
                if ef:
                    in_specs += (pspec,)      # per-machine EF residual
                    out_specs += (pspec,)
        elif cfg.mode == "halo":
            in_specs = (P(), P(), pspec, pspec, pspec, pspec, pspec, pspec,
                        P(), pspec, pspec, pspec, pspec)
            out_specs = (P(), P(), P())
            shard_body = shard_halo
        else:
            in_specs = (P(), P(), pspec, pspec, pspec, pspec, pspec, pspec,
                        P())
            out_specs = (P(), P(), P())
            shard_body = shard_sync
        self._round = self._jit_counting(jax.shard_map(
            shard_body, mesh=self.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False))

    # ------------------------------------------------------ correction phase
    def _build_correction(self):
        grad_fn = self._grad_fn
        server_opt = self.server_opt

        def corr_scan(params, server_state, feats, labels, tables, masks,
                      batches, bmasks, agg):
            """S server steps on uniform global batches (Alg. 2 lines 13-18).

            ``agg`` carries the correction phase's aggregation-layout
            operands (:mod:`repro.models.gnn.agg`) — the full-neighbor
            forward is exactly the regime where the edge-centric layouts
            replace the padded dense gather; ``None`` keeps the padded path.
            """
            per_step_tables = tables.ndim == 3  # sampling-at-correction

            def one(carry, xs):
                p, so = carry
                if per_step_tables:
                    table, mask, batch, bmask = xs
                else:
                    batch, bmask = xs
                    table, mask = tables, masks
                with jax.named_scope("correction_step"):
                    loss, grads = grad_fn(p, feats, table, mask, batch,
                                          labels, bmask, agg)
                    upd, so = server_opt.update(grads, so, p)
                    return (apply_updates(p, upd), so), loss

            xs = ((tables, masks, batches, bmasks) if per_step_tables
                  else (batches, bmasks))
            (params, server_state), losses = jax.lax.scan(
                one, (params, server_state), xs)
            return params, server_state, jnp.mean(losses)

        def counted_correction(*args):
            # trace-time side effect, same discipline as _jit_counting: a
            # layout change retraces once, never per round
            self._corr_traces.count(trace_signature(args))
            return corr_scan(*args)

        self._corr = jax.jit(counted_correction)

    # ------------------------------------------------------------------- API
    def init_state(self, params) -> EngineState:
        cfg = self.cfg
        if cfg.mode == "local" and cfg.reset_local_opt:
            # per-round optimizer state is rebuilt from the incoming params
            # inside the round; carry only a scalar placeholder
            o = jnp.zeros(())
        else:
            o = self.local_opt.init(params)
            if cfg.mode == "local":
                o = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(
                        x[None], (cfg.num_machines,) + x.shape), o)
        server = (self.server_opt.init(params) if cfg.with_correction
                  else None)
        residual = None
        if cfg.mode == "local" and cfg.compression == "int8_ef":
            residual = jax.tree_util.tree_map(
                lambda x: jnp.zeros((cfg.num_machines,) + x.shape, x.dtype),
                params)
        self._comm_calls = 0  # restart the stochastic-rounding key stream
        return EngineState(params=params, local_opt_state=o,
                           server_opt_state=server, comm_residual=residual)

    def run_round(self, state: EngineState, feats, labels,
                  inputs: RoundInputs) -> tuple:
        """Execute one full round; returns ``(state, metrics)``."""
        svalid = inputs.step_valid
        if svalid is None:
            svalid = jnp.ones((inputs.tables.shape[1],), jnp.float32)
        args = (state.params, state.local_opt_state, feats, labels,
                inputs.tables, inputs.masks, inputs.batches, inputs.bmasks,
                svalid)
        if self.cfg.mode == "halo":
            halo = (inputs.halo_send_idx, inputs.halo_recv_idx,
                    inputs.halo_dest_idx, inputs.halo_recv_valid)
            if any(h is None for h in halo):
                raise ValueError("mode='halo' requires the halo_* index "
                                 "tables in RoundInputs (see "
                                 "repro.graph.halo.HaloProgram)")
            args += halo
        ef = self.cfg.mode == "local" and self.cfg.compression == "int8_ef"
        if self._comm_stochastic:
            args += (jax.random.fold_in(self._comm_key, self._comm_calls),)
            self._comm_calls += 1
        if ef:
            args += (state.comm_residual,)
            params, opt_state, loss, residual = self._round(*args)
        else:
            residual = state.comm_residual
            params, opt_state, loss = self._round(*args)
        # metrics stay DEVICE scalars: materializing them here would block
        # the host on the round's dispatch and defeat run_schedule's
        # sample/compute overlap — the driver floats them after issuing the
        # next round's (prefetched) sample
        metrics = {"local_loss": loss}
        server_state = state.server_opt_state
        # S=0 corrections: skip entirely (a 0-length scan would mean-reduce
        # an empty losses array to NaN)
        if (self.cfg.with_correction and inputs.corr_batches is not None
                and inputs.corr_batches.shape[0] > 0):
            params, server_state, closs = self._corr(
                params, server_state, inputs.corr_feats, inputs.corr_labels,
                inputs.corr_tables, inputs.corr_masks, inputs.corr_batches,
                inputs.corr_bmasks, inputs.corr_agg)
            metrics["corr_loss"] = closs
        return EngineState(params=params, local_opt_state=opt_state,
                           server_opt_state=server_state,
                           comm_residual=residual), metrics


# --------------------------------------------------------------------------
# Schedule driver — byte/step accounting shared by every strategy
# --------------------------------------------------------------------------
def pad_inputs_to_bucket(inputs: RoundInputs, k_pad: int) -> RoundInputs:
    """Pad a round's K axis to ``k_pad``, flagging the tail as masked.

    Tables/masks/batches/bmasks are zero-padded along the step axis (zero
    bmasks already make the padded losses inert) and ``step_valid`` marks
    the real prefix, so the padded steps execute as optimizer no-ops
    (:func:`repro.optim.optimizers.masked_update`).

    Inputs that already carry a ``step_valid`` flag (the device sampler
    draws directly at the bucketed length, marking the real prefix itself)
    pass through untouched — padding them again would double-pad.
    """
    k = int(inputs.tables.shape[1])
    if inputs.step_valid is not None:
        if k != k_pad:
            raise ValueError(
                f"inputs carry step_valid at K={k} but the bucket length is "
                f"{k_pad}; pre-padded inputs must be sampled at the bucketed "
                "length")
        return inputs
    if k_pad < k:
        raise ValueError(f"bucket length {k_pad} < scheduled K {k}")
    svalid = jnp.concatenate([jnp.ones((k,), jnp.float32),
                              jnp.zeros((k_pad - k,), jnp.float32)])
    if k_pad == k:
        return dataclasses.replace(inputs, step_valid=svalid)

    def padk(x):
        widths = [(0, 0), (0, k_pad - k)] + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(jnp.asarray(x), widths)

    return dataclasses.replace(
        inputs, tables=padk(inputs.tables), masks=padk(inputs.masks),
        batches=padk(inputs.batches), bmasks=padk(inputs.bmasks),
        step_valid=svalid)


@dataclasses.dataclass
class ResumePoint:
    """Where a checkpointed run left off (see :mod:`repro.checkpoint`).

    ``state`` is the restored engine state, ``history`` the History as of
    the checkpointed round, ``start_round`` the first round still to
    EXECUTE (checkpoint round + 1).  The caller must have restored the
    program's internal state (sub-states, retrace signatures, key-stream
    cursors) before calling :func:`run_schedule` — with a ResumePoint the
    driver skips ``program.init_state`` entirely.
    """

    state: Any
    history: History
    start_round: int


def _per_round_fn(fn: Callable) -> Callable[[int, int], Any]:
    """Normalize an accounting callback to ``fn(r, k)``.

    Legacy strategy code passes per-K lambdas ``fn(k)``; plan lowering
    (:mod:`repro.core.plan`) needs the round index too (a hybrid plan's
    cost depends on WHICH round runs, not just its length), so callables
    with two REQUIRED positional parameters receive ``(r, k)``.  Defaulted
    parameters don't count — ``lambda k, pb=x: …`` stays a per-K callback.
    """
    try:
        required = sum(
            1 for p in inspect.signature(fn).parameters.values()
            if p.default is inspect.Parameter.empty
            and p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD))
    except (TypeError, ValueError):
        required = 1
    if required >= 2:
        return fn
    return lambda r, k: fn(k)


def run_schedule(program: RoundProgram, init_params, feats, labels,
                 sample_fn: Callable[[int, int], RoundInputs],
                 schedule: List[int],
                 evaluate: Callable[[Any], tuple],
                 name: str,
                 bytes_per_round: Callable[[int], float],
                 steps_per_round: Callable[[int], int],
                 meta: Optional[Dict] = None,
                 bucketing: Optional[KBucketing] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_keep: int = 3,
                 prefetch: bool = False,
                 checkpoint_hook: Optional[Any] = None,
                 resume: Optional[ResumePoint] = None) -> History:
    """Run ``schedule[r]`` local steps per round r through the engine.

    ``sample_fn(round, k)`` performs the host-side batched sampling for one
    round; ``evaluate(params) -> (loss, score)`` is the server's full-graph
    validation; ``bytes_per_round(k)`` / ``steps_per_round(k)`` encode each
    strategy's communication/step cost so History accounting is uniform
    (both also accept ``(r, k)`` — see :func:`_per_round_fn`).  ``program``
    is duck-typed: anything with ``init_state`` / ``run_round`` /
    ``num_retraces`` works, which is how :mod:`repro.core.plan` dispatches
    per-round over several engine programs behind one facade.

    Uniform per-round metrics land in ``meta``: ``local_loss`` (every
    round), ``corr_loss`` + ``corr_rounds`` (rounds where a server
    correction actually ran), ``round_seconds`` (host wall time from the
    round's start to its evaluated result — the first round's includes
    compilation), and ``masked_steps``/``num_retraces`` are always present
    (0 / program count when unbucketed).

    With a ``bucketing`` policy, each round's inputs are padded to the
    bucketed scan length and the tail runs as masked no-op steps — host
    sampling, RNG streams, byte and step accounting all still use the REAL
    K, so the trajectory is identical to the unbucketed run while the
    engine compiles only one program per bucket.  ``hist.meta`` records
    ``num_retraces``, the bucket grid used and the total masked (padded)
    steps it cost.

    ``checkpoint_dir`` is the params-export hook of the train→serve story:
    after each round's evaluation the averaged/corrected
    ``EngineState.params`` are written through
    :func:`repro.checkpoint.store.save_checkpoint` (step = round, newest
    ``checkpoint_keep`` retained), ready for
    ``repro.serving.gnn.GNNServingEngine.from_checkpoint``.

    ``prefetch=True`` double-buffers the sampling: round r+1's
    ``sample_fn`` is issued right after round r's compute is DISPATCHED but
    before anything blocks on its results (metrics floats, evaluation), so
    a device-resident sampler's draw overlaps the in-flight scan.  Rounds
    are still consumed strictly in order and each round's inputs are fully
    materialized before its own ``run_round``, so with a host sampler the
    draw order — and therefore the trajectory — is bit-identical to the
    synchronous loop.

    ``checkpoint_hook`` is the full-state periodic-checkpoint tap (see
    :mod:`repro.checkpoint.manager`): ``hook.after_round(r, state)`` fires
    right after round r's dispatch and BEFORE round r+1's prefetched sample
    — the one point where the host sampler's RNG streams sit exactly at
    "rounds 1..r drawn" — and ``hook.commit(r, state, hist)`` fires after
    round r's History rows land (the evaluation has already blocked on the
    round, so the snapshot's device→host transfer costs nothing extra).
    ``resume`` (a :class:`ResumePoint`) continues a checkpointed run:
    ``program.init_state`` is skipped (the caller restored the program),
    rounds before ``resume.start_round`` are skipped, and History/byte/step
    accumulators continue from the restored History — the completed run is
    bit-identical to one that was never interrupted.
    """
    bpr = _per_round_fn(bytes_per_round)
    spr = _per_round_fn(steps_per_round)
    if resume is None:
        state = program.init_state(init_params)
        hist = History(strategy=name, meta=dict(meta or {}))
        start = 1
    else:
        state = resume.state
        hist = resume.history
        start = resume.start_round
    hist.meta.setdefault("local_loss", [])
    hist.meta.setdefault("corr_loss", [])
    hist.meta.setdefault("corr_rounds", [])
    hist.meta.setdefault("round_seconds", [])
    bytes_cum = float(hist.bytes_cum[-1]) if hist.bytes_cum else 0.0
    steps_cum = int(hist.steps_cum[-1]) if hist.steps_cum else 0

    def draw(r, k):
        with span("sample"):
            inputs = sample_fn(r, k)
            if bucketing is not None:
                inputs = pad_inputs_to_bucket(inputs,
                                              bucketing.pad_length(k))
        return inputs

    def read(x) -> float:
        with span("read"):
            return float(x)

    pending = (draw(start, schedule[start - 1])
               if (prefetch and start <= len(schedule)) else None)
    for r, k in enumerate(schedule, start=1):
        if r < start:
            continue
        with jax.profiler.StepTraceAnnotation("llcg.round", step_num=r):
            t0 = time.perf_counter()
            inputs = pending if prefetch else draw(r, k)
            with span("dispatch"):
                state, metrics = program.run_round(state, feats, labels,
                                                   inputs)
            if checkpoint_hook is not None:
                # BEFORE the prefetch draw: the snapshot must capture the
                # RNG streams at "rounds 1..r drawn, nothing beyond"
                with span("checkpoint"):
                    checkpoint_hook.after_round(r, state)
            if prefetch:
                # the overlap: round r's scan is in flight, nothing has
                # blocked on it yet — issue round r+1's sample NOW
                pending = (draw(r + 1, schedule[r]) if r < len(schedule)
                           else None)
            lloss = metrics.get("local_loss")
            hist.meta["local_loss"].append(
                None if lloss is None else read(lloss))
            if "corr_loss" in metrics:
                hist.meta["corr_loss"].append(read(metrics["corr_loss"]))
                hist.meta["corr_rounds"].append(r)
            bytes_cum += bpr(r, k)
            steps_cum += spr(r, k)
            with span("evaluate"):
                loss, score = evaluate(state.params)
            hist.meta["round_seconds"].append(time.perf_counter() - t0)
            hist.rounds.append(r)
            hist.steps_cum.append(steps_cum)
            hist.val_score.append(score)
            hist.train_loss.append(loss)
            hist.bytes_cum.append(bytes_cum)
            if checkpoint_dir:
                from repro.checkpoint.store import save_checkpoint
                with span("checkpoint"):
                    save_checkpoint(checkpoint_dir, r, state.params,
                                    extra={"strategy": name, "round": r,
                                           "val_score": score},
                                    keep=checkpoint_keep)
            if checkpoint_hook is not None:
                with span("checkpoint"):
                    checkpoint_hook.commit(r, state, hist)
    hist.meta["final_params"] = state.params
    hist.meta["num_retraces"] = program.num_retraces
    hist.meta["num_corr_retraces"] = getattr(program, "num_corr_retraces", 0)
    if bucketing is not None:
        hist.meta["bucket_lengths"] = bucketing.bucket_lengths(schedule)
        hist.meta["masked_steps"] = bucketing.masked_steps(schedule)
    else:
        hist.meta["masked_steps"] = 0
    hist.meta["distinct_k"] = len(set(schedule))
    return hist
