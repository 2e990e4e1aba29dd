"""Composable TrainPlan API: strategies as declarative round-phase plans.

The paper's algorithms differ only in how they compose four primitives —
K local steps, the periodic parameter average, S server corrections
(Eq. 2), and the per-step cut-node halo exchange.  This module makes that
taxonomy the public API: a :class:`TrainPlan` is a tuple of
:class:`RoundPhase` specs (``local_steps`` | ``averaging`` | ``correction``
| ``halo_exchange``) over grouped sub-configs, and ONE builder —
:func:`build_trainer` — lowers any plan onto the existing
:class:`repro.core.engine.RoundProgram` / :func:`repro.core.engine.
run_schedule` machinery on either backend (``backend="vmap"`` simulation or
``backend="shard_map"`` device-per-machine).

The four classic strategies are one-line canned plans
(:func:`psgd_pa_plan`, :func:`llcg_plan`, :func:`ggs_plan`,
:func:`single_machine_plan`) and reproduce the legacy
``run_psgd_pa/run_llcg/run_ggs/run_single_machine`` trajectories
bit-for-bit — those functions are now thin shims over this module
(:mod:`repro.core.strategies`).  Compositions the old API could not express
are ordinary plans here, e.g.::

    # server correction only every 2nd round
    TrainPlan(phases=(local_steps(), averaging(), correction(every=2)), ...)

    # halo-exchange (GGS) rounds to warm up, then cheap LLCG rounds
    TrainPlan(phases=(halo_exchange(first=3),
                      local_steps(after=3), averaging(after=3),
                      correction(after=3)), ...)

    # strategy switching driven by the K·ρ^r schedule: exact halo rounds
    # while K is small, local rounds once K is large
    big = lambda r, k: k >= 8
    TrainPlan(phases=(halo_exchange(when=lambda r, k: k < 8),
                      local_steps(when=big), averaging(when=big),
                      correction(when=big)), ...)

Each scheduled round is lowered independently: the set of phases active at
round ``r`` (scheduled length ``k``) picks the engine round mode, the
optimizer-state threading, the host sampling path, and the byte/step
accounting, so ``History`` stays uniform across every composition.

Per-round phase activity composes four declarative gates —
``every`` / ``first`` / ``after`` / ``when(r, k)`` — all of which must pass.

:class:`RoundSampler` absorbs the per-strategy sampling contexts the old
``run_*`` functions each carried (``_Context`` and ``GGSContext``): one
object owns the partition, shard loaders, shared host RNG, padded
per-machine views, the server's full-neighbor eval/correction tables, and
(built on demand) the extended-graph views + :class:`repro.graph.halo.
HaloProgram` of the halo rounds.  RNG draw order is IDENTICAL to the legacy
contexts, which is what makes the canned plans bit-exact.

``DistConfig`` — the legacy flat config — lives here as a deprecation shim:
it validates every field at construction (unknown ``optimizer`` /
``bucket_mode`` / ``partition_method`` raise immediately with the allowed
values instead of deep inside a run) and :meth:`DistConfig.specs` regroups
it into the typed sub-configs (:class:`LocalSpec`, :class:`ServerSpec`,
:class:`CommSpec`, :class:`SamplerSpec`, :class:`ScheduleSpec`,
:class:`CompileSpec`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import (
    CheckpointManager, TraceCounter, digest_json, trace_signature,
)
from repro.comm.compress import averaging_payload_bytes
from repro.core.engine import (
    EngineConfig, EngineState, History, ResumePoint, RoundInputs,
    RoundProgram, run_schedule, span,
)
from repro.core.machine import make_eval_fn, make_machine_step
from repro.core.schedules import KBucketing, local_epoch_schedule
from repro.data.graph_loader import make_shard_loaders, sample_round
from repro.graph.datasets import SyntheticDataset
from repro.graph.halo import build_halo_plan, build_halo_program, ext_fanout
from repro.graph.partition import PARTITION_METHODS, partition_graph
from repro.graph.sampling import (
    DeviceCSR, _all_nodes_plan, build_device_csr, sample_minibatch,
    sample_minibatch_batched, sample_neighbors, sample_neighbors_batched,
    sample_round_device,
)
from repro.models.gnn.agg import (
    LAYOUTS as AGG_LAYOUTS, build_agg_operands, bucketed_operands,
    choose_layout, degree_buckets, full_table_stats,
)
from repro.models.gnn.model import GNNModel
from repro.optim import OPTIMIZERS, Optimizer, make_optimizer
from repro.utils.pytree import tree_bytes


#: Round-phase kinds — the paper's composable primitives.
PHASE_KINDS = ("local_steps", "averaging", "correction", "halo_exchange")
#: K-bucketing grids (:class:`repro.core.schedules.KBucketing`).
BUCKET_MODES = ("geometric", "fit")
#: Engine backends :func:`build_trainer` lowers onto.
BACKENDS = ("vmap", "shard_map")
#: Where round sampling executes (:class:`SamplerSpec`).
PLACEMENTS = ("host", "device")


def _check(cond: bool, msg: str):
    if not cond:
        raise ValueError(msg)


# --------------------------------------------------------------------------
# Grouped sub-configs (the split of the old flat DistConfig)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LocalSpec:
    """The K-local-steps phase: per-machine optimizer + step budget."""

    local_k: int = 4                 # K
    batch_size: int = 32             # B_L
    lr: float = 1e-2                 # η
    optimizer: str = "adam"          # paper uses ADAM (App. A.2)
    agg_layout: str = "padded"       # "padded" | "auto" (local rounds run
                                     # sampled narrow tables, where auto
                                     # resolves to padded — the edge-centric
                                     # layouts encode the FULL edge set)

    def __post_init__(self):
        _check(self.local_k >= 1, "local_k must be ≥ 1")
        _check(self.batch_size >= 1, "batch_size must be ≥ 1")
        _check(self.lr > 0, "lr must be > 0")
        _check(self.optimizer in OPTIMIZERS,
               f"unknown optimizer {self.optimizer!r}; "
               f"choose one of {OPTIMIZERS}")
        _check(self.agg_layout in ("padded", "auto"),
               f"LocalSpec.agg_layout {self.agg_layout!r} is not available: "
               "local rounds train on sampled (subsampled/narrowed) "
               "neighbor tables, which the edge-centric layouts cannot "
               "represent — they encode the full edge set.  Use 'padded' "
               "(or 'auto', which resolves to padded here); put 'csr'/"
               "'bcsr_kernel' on ServerSpec.agg_layout for the "
               "full-neighbor correction phase")


@dataclasses.dataclass(frozen=True)
class ServerSpec:
    """The server-correction phase (Eq. 2 / Alg. 2 lines 13-18)."""

    correction_steps: int = 1        # S
    server_batch_size: int = 64      # B_S
    server_lr: Optional[float] = None  # γ (None → local lr η)
    correction_sampling: bool = False  # App. A "sampling at correction"
    max_cut_minibatch: bool = False    # App. A.3 ablation
    agg_layout: str = "padded"       # aggregation layout of the correction
                                     # forward (repro.models.gnn.agg): the
                                     # full-neighbor regime where "csr"/
                                     # "auto" replace the padded gather

    def __post_init__(self):
        _check(self.correction_steps >= 0, "correction_steps must be ≥ 0")
        _check(self.server_batch_size >= 1, "server_batch_size must be ≥ 1")
        _check(self.server_lr is None or self.server_lr > 0,
               "server_lr must be > 0 (or None for the local lr)")
        _check(self.agg_layout in AGG_LAYOUTS,
               f"unknown agg_layout {self.agg_layout!r}; "
               f"choose one of {AGG_LAYOUTS}")
        _check(not (self.correction_sampling
                    and self.agg_layout in ("csr", "bcsr_kernel")),
               "correction_sampling draws per-step subsampled tables, which "
               f"the {self.agg_layout!r} layout cannot represent (it "
               "encodes the full edge set) — use agg_layout='padded' or "
               "'auto' with the sampling-at-correction ablation")


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """Topology + communication semantics.

    ``compression`` / ``halo_compression`` select the payload codecs of
    :mod:`repro.comm.compress` for the two collectives that define LLCG's
    cost model: the averaging rounds' parameter-delta exchange
    (``none | bf16 | int8 | int8_ef`` — int8 codecs use stochastic
    rounding; ``int8_ef`` carries the per-machine error-feedback residual
    so the averaged iterates converge to the uncompressed fixed point) and
    the halo rounds' cut-node feature ``all_gather``
    (``none | bf16 | int8``, deterministic rounding).  ``"none"`` keeps
    both collectives on the pre-compression code path bit-identically, and
    all byte accounting (``PlanTrainer.accounting``, ``History`` bytes,
    the dryrun HLO cross-check) prices the compressed wire format.
    """

    num_machines: int = 8
    partition_method: str = "bfs"
    host_halo: bool = False          # legacy GGS: host-materialized halo
    compression: str = "none"        # averaging-round param-delta codec
    halo_compression: str = "none"   # halo-round feature codec

    def __post_init__(self):
        from repro.comm.compress import COMPRESSIONS, HALO_COMPRESSIONS
        _check(self.num_machines >= 1, "num_machines must be ≥ 1")
        _check(self.partition_method in PARTITION_METHODS,
               f"unknown partition_method {self.partition_method!r}; "
               f"choose one of {PARTITION_METHODS}")
        _check(self.compression in COMPRESSIONS,
               f"unknown compression {self.compression!r}; "
               f"choose one of {COMPRESSIONS}")
        _check(self.halo_compression in HALO_COMPRESSIONS,
               f"unknown halo_compression {self.halo_compression!r}; "
               f"choose one of {HALO_COMPRESSIONS} (error feedback needs "
               "a persistent per-machine residual, which per-step feature "
               "buffers don't carry)")
        _check(not (self.host_halo and self.halo_compression != "none"),
               "host_halo materializes raw f32 halo features on the host — "
               "halo_compression requires the executed device exchange "
               "(host_halo=False)")


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """Neighbor sampling (Eq. 4) + where the round draw executes.

    ``placement="host"`` is the legacy vectorized-numpy path and preserves
    its RNG streams bit-exactly.  ``placement="device"`` moves the whole
    round draw onto the accelerator (:func:`repro.graph.sampling.
    sample_round_device`, its own documented key-folding stream) and lets
    the schedule driver double-buffer: round r+1's sample is dispatched
    while round r's scan runs.  ``overlap`` controls that prefetch
    (``None`` → enabled exactly when placement is "device").  Host mode is
    still required for ``rng_compat`` legacy-stream replay.
    """

    fanout: Optional[int] = 10       # None = full neighbors
    fanout_ratio: Optional[float] = None
    full_graph: bool = False         # centralized reference: sample the
                                     # UNpartitioned graph (requires P=1)
    placement: str = "host"          # "host" | "device"
    overlap: Optional[bool] = None   # None → (placement == "device")

    def __post_init__(self):
        _check(self.fanout is None or self.fanout >= 1,
               "fanout must be ≥ 1 or None (full neighbors)")
        _check(self.fanout_ratio is None or 0.0 < self.fanout_ratio <= 1.0,
               "fanout_ratio must be in (0, 1]")
        _check(self.placement in PLACEMENTS,
               f"unknown placement {self.placement!r}; "
               f"choose one of {PLACEMENTS}")

    @property
    def resolved_overlap(self) -> bool:
        return (self.placement == "device" if self.overlap is None
                else bool(self.overlap))


@dataclasses.dataclass(frozen=True)
class ScheduleSpec:
    """How many rounds, and how K grows (Section 3.1).

    ``k_schedule`` pins an explicit per-round step count; otherwise round r
    runs ``local_k·ρ^r`` steps when ρ>1 and a fixed ``local_k`` when ρ=1.
    """

    rounds: int = 20
    rho: float = 1.0                 # ρ (>1 → exponential LLCG schedule)
    k_schedule: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        _check(self.rounds >= 1, "rounds must be ≥ 1")
        _check(self.rho >= 1.0, "ρ must be ≥ 1 (ρ=1 is the fixed schedule)")
        if self.k_schedule is not None:
            _check(len(self.k_schedule) == self.rounds,
                   "k_schedule length must equal rounds")
            _check(all(k >= 1 for k in self.k_schedule),
                   "k_schedule entries must be ≥ 1")

    def resolve(self, base_k: int) -> List[int]:
        if self.k_schedule is not None:
            return list(self.k_schedule)
        if self.rho > 1.0:
            return local_epoch_schedule(base_k, self.rho, self.rounds)
        return [base_k] * self.rounds


@dataclasses.dataclass(frozen=True)
class CompileSpec:
    """Tracing/compatibility knobs (no effect on the math).

    The persistent compilation cache is process-global, not per plan: see
    :func:`enable_compilation_cache`.
    """

    rng_compat: bool = False         # replay the pre-vectorization RNG
    k_bucketing: bool = False        # pad K to buckets → O(log) retraces
    bucket_growth: int = 2
    bucket_mode: str = "geometric"

    def __post_init__(self):
        _check(self.bucket_growth >= 2, "bucket_growth must be ≥ 2")
        _check(self.bucket_mode in BUCKET_MODES,
               f"unknown bucket_mode {self.bucket_mode!r}; "
               f"choose one of {BUCKET_MODES}")

    def bucketing_for(self, schedule: List[int],
                      base_k: int) -> Optional[KBucketing]:
        if not self.k_bucketing:
            return None
        if self.bucket_mode == "fit":
            return KBucketing.fit(schedule, min_len=base_k,
                                  growth=self.bucket_growth)
        return KBucketing(min_len=base_k, growth=self.bucket_growth)


@dataclasses.dataclass(frozen=True)
class CheckpointSpec:
    """Preemption-safe full-state checkpointing (no effect on the math).

    Every ``every``-th round, the trainer snapshots the ENTIRE training
    state — params, per-program optimizer states, the error-feedback
    ``comm_residual``, the shared server-optimizer state, every host RNG
    stream position, the round cursor, retrace signatures, and ``History``
    — through :class:`repro.checkpoint.manager.CheckpointManager` under
    ``dir``.  A run killed at ANY instant resumes from the latest valid
    checkpoint (``PlanTrainer.run(resume_from=...)`` /
    :func:`repro.launch.train.resume`) bit-identical to an uninterrupted
    run.  ``async_=True`` (default) moves serialization + fsync to a
    background writer thread; the bounded ``queue_size`` makes a slow disk
    backpressure the trainer instead of dropping checkpoints.
    """

    dir: str
    every: int = 1
    keep: int = 3
    async_: bool = True
    queue_size: int = 2

    def __post_init__(self):
        _check(bool(self.dir), "CheckpointSpec.dir must be a directory path")
        _check(self.every >= 1, "CheckpointSpec.every must be ≥ 1")
        _check(self.keep >= 0,
               "CheckpointSpec.keep must be ≥ 0 (0 = keep everything)")
        _check(self.queue_size >= 1, "CheckpointSpec.queue_size must be ≥ 1")


#: The compilation cache's directory when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed path inside the checkout.  The path is part of the
#: cache key, so a temporary or per-run directory would never hit.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *[os.pardir] * 3,
    ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (jax reads the
    variable itself, and no other is set here); otherwise
    :data:`DEFAULT_COMPILATION_CACHE_DIR`.  Process-global: call it before
    the first compile, since jax initialises the cache once per process.
    The size/time floors are zeroed so every program is cached.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.normpath(DEFAULT_COMPILATION_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# --------------------------------------------------------------------------
# RoundPhase — one composable primitive + its per-round activity gates
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RoundPhase:
    """One primitive of the round, active on a declarative subset of rounds.

    A phase runs at round r (1-based, scheduled length k) iff ALL gates
    pass: ``r % every == 0``, ``r ≤ first`` (when set), ``r > after``, and
    ``when(r, k)`` (when set — this is the schedule-driven switch: the
    predicate sees the round's scheduled K).
    """

    kind: str
    every: int = 1
    first: Optional[int] = None
    after: int = 0
    when: Optional[Callable[[int, int], bool]] = None
    reset_opt: bool = True           # local_steps only: Alg. 2 line 3

    def __post_init__(self):
        _check(self.kind in PHASE_KINDS,
               f"unknown phase kind {self.kind!r}; "
               f"choose one of {PHASE_KINDS}")
        _check(self.every >= 1, "every must be ≥ 1")
        _check(self.first is None or self.first >= 0, "first must be ≥ 0")
        _check(self.after >= 0, "after must be ≥ 0")
        _check(self.kind == "local_steps" or self.reset_opt,
               f"reset_opt=False applies only to local_steps phases "
               f"(got kind={self.kind!r}; halo rounds always thread their "
               "per-step optimizer state)")

    def active(self, r: int, k: int) -> bool:
        return (r % self.every == 0
                and (self.first is None or r <= self.first)
                and r > self.after
                and (self.when is None or bool(self.when(r, k))))

    def describe(self) -> Dict:
        d = {"kind": self.kind, "every": self.every, "first": self.first,
             "after": self.after, "when": bool(self.when)}
        if self.kind == "local_steps":
            d["reset_opt"] = self.reset_opt
        return d


def local_steps(**kw) -> RoundPhase:
    """K dependency-free local steps per machine (Alg. 1/2 lines 3-9)."""
    return RoundPhase("local_steps", **kw)


def averaging(**kw) -> RoundPhase:
    """The end-of-round parameter-average collective (Alg. 1/2 line 12)."""
    return RoundPhase("averaging", **kw)


def correction(**kw) -> RoundPhase:
    """S global server-correction steps (Alg. 2 lines 13-18)."""
    return RoundPhase("correction", **kw)


def halo_exchange(**kw) -> RoundPhase:
    """GGS rounds: per-step cut-node feature exchange + per-step gradient
    averaging on the extended (local ∪ halo) graphs."""
    return RoundPhase("halo_exchange", **kw)


# --------------------------------------------------------------------------
# TrainPlan
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TrainPlan:
    """A declarative training strategy: phases × grouped sub-configs."""

    phases: Tuple[RoundPhase, ...]
    local: LocalSpec = LocalSpec()
    server: ServerSpec = ServerSpec()
    comm: CommSpec = CommSpec()
    sampler: SamplerSpec = SamplerSpec()
    schedule: ScheduleSpec = ScheduleSpec()
    compile: CompileSpec = CompileSpec()
    name: str = "plan"
    seed: int = 0
    checkpoint_dir: Optional[str] = None  # per-round params export (serving)
    checkpoint: Optional[CheckpointSpec] = None  # full-state resume snapshots

    def __post_init__(self):
        if not isinstance(self.phases, tuple):
            object.__setattr__(self, "phases", tuple(self.phases))
        _check(len(self.phases) > 0, "a TrainPlan needs at least one phase")
        if self.sampler.full_graph:
            _check(self.comm.num_machines == 1,
                   "sampler.full_graph (centralized reference) requires "
                   "num_machines=1")
            _check(all(p.kind != "halo_exchange" for p in self.phases),
                   "sampler.full_graph cannot be combined with "
                   "halo_exchange phases")
        _check(not (self.sampler.placement == "device"
                    and self.compile.rng_compat),
               "sampler.placement='device' draws from the documented "
               "jax.random stream and cannot replay the legacy numpy "
               "streams — rng_compat requires placement='host'")

    def describe(self) -> Dict:
        """JSON-able summary for ``History.meta`` (callables elided)."""
        return {
            "name": self.name,
            "phases": [p.describe() for p in self.phases],
            "local": dataclasses.asdict(self.local),
            "server": dataclasses.asdict(self.server),
            "comm": dataclasses.asdict(self.comm),
            "sampler": dataclasses.asdict(self.sampler),
            "schedule": dataclasses.asdict(self.schedule),
            "compile": dataclasses.asdict(self.compile),
            "seed": self.seed,
            "checkpoint": (dataclasses.asdict(self.checkpoint)
                           if self.checkpoint is not None else None),
        }


@dataclasses.dataclass(frozen=True)
class RoundDesc:
    """One scheduled round after lowering: mode, threading and accounting."""

    r: int
    k: int
    kind: str                        # data path: "local" | "ext" | "full"
    mode: str                        # engine mode: "local" | "sync" | "halo"
    averaging: bool
    correction: bool
    reset_opt: bool

    @property
    def program_key(self) -> Tuple:
        return (self.mode, self.reset_opt if self.mode == "local" else None)


def lower_plan(plan: TrainPlan) -> List[RoundDesc]:
    """Resolve the schedule and per-round phase activity into RoundDescs.

    Pure and cheap — all composition errors (a round with no compute phase,
    local_steps+halo_exchange in the same round, missing averaging on >1
    machine) surface here, before any data or program is built.
    """
    P = plan.comm.num_machines
    descs = []
    for r, k in enumerate(plan.schedule.resolve(plan.local.local_k), 1):
        active = [p for p in plan.phases if p.active(r, k)]
        kinds = {p.kind for p in active}
        if "halo_exchange" in kinds:
            _check("local_steps" not in kinds,
                   f"round {r}: local_steps and halo_exchange cannot both "
                   "be active — a round is either K independent local steps "
                   "or per-step synchronized halo rounds")
            _check("averaging" not in kinds,
                   f"round {r}: halo_exchange already averages gradients "
                   "every step; drop the averaging phase on halo rounds")
            descs.append(RoundDesc(
                r=r, k=k, kind="ext",
                mode="sync" if plan.comm.host_halo else "halo",
                averaging=True, correction="correction" in kinds,
                reset_opt=False))
            continue
        _check("local_steps" in kinds,
               f"round {r}: no compute phase is active — every round needs "
               "local_steps or halo_exchange")
        avg = "averaging" in kinds
        _check(avg or P == 1,
               f"round {r}: local_steps on {P} machines requires the "
               "averaging phase (the engine's round always ends in the "
               "parameter-average collective); add averaging() or set "
               "num_machines=1")
        resets = {p.reset_opt for p in active if p.kind == "local_steps"}
        _check(len(resets) == 1,
               f"round {r}: conflicting reset_opt on active local_steps "
               "phases")
        descs.append(RoundDesc(
            r=r, k=k, kind="full" if plan.sampler.full_graph else "local",
            mode="local", averaging=avg,
            correction="correction" in kinds, reset_opt=resets.pop()))
    return descs


def _f32_mask(shape, fill: float = 1.0) -> np.ndarray:
    """One float32 mask/bmask buffer (validity weights are f32 everywhere).

    Every sampler path hand-rolled its own ``np.ones``/``np.zeros`` mask;
    this is the single constructor — ``fill=1.0`` for valid-everywhere
    batch masks, ``fill=0.0`` for buffers the sampling loop fills in.
    """
    return np.full(shape, fill, np.float32)


# --------------------------------------------------------------------------
# RoundSampler — unified host-side sampling (absorbs _Context/GGSContext)
# --------------------------------------------------------------------------
class RoundSampler:
    """Partitioned views + host RNG streams + jit'd helpers for any plan.

    One instance serves every round kind: padded per-machine local views
    (``feats_j``/``labels_j``), the server's full-neighbor eval/correction
    tables, the single shared host RNG the legacy contexts used (identical
    draw order — the bit-exactness anchor of the canned plans), and, built
    on demand by :meth:`ensure_halo`, the extended-graph views and
    :class:`~repro.graph.halo.HaloProgram` driving halo rounds.
    """

    def __init__(self, data: SyntheticDataset, model: GNNModel,
                 plan: TrainPlan, mesh=None):
        self.data, self.model, self.plan = data, model, plan
        comm, smp, loc, srv = plan.comm, plan.sampler, plan.local, plan.server
        self.num_machines = comm.num_machines
        self.rng_compat = plan.compile.rng_compat
        self.batch_size = loc.batch_size
        self.placement = smp.placement
        self.mesh = mesh
        self._machine_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._machine_sharding = NamedSharding(mesh,
                                                   PartitionSpec("machine"))
        self.partition = partition_graph(data.graph, comm.num_machines,
                                         method=comm.partition_method,
                                         seed=plan.seed)
        self.loaders, self.server_sampler = make_shard_loaders(
            data, self.partition, fanout=smp.fanout,
            fanout_ratio=smp.fanout_ratio, seed=plan.seed,
            rng_compat=self.rng_compat)
        self.rng = np.random.default_rng(plan.seed + 1)

        P = comm.num_machines
        self.n_max = max(len(self.partition.part_nodes[p]) for p in range(P))
        # pad width must cover every machine's fanout: with fanout_ratio the
        # per-machine samplers resolve different fanouts from their local
        # max degrees, and a narrower pad would truncate sampled columns
        self.fanout = max(ld.sampler.fanout for ld in self.loaders)
        d = data.feature_dim
        self.feats = np.zeros((P, self.n_max, d), np.float32)
        self.labels = np.zeros((P, self.n_max), np.int32)
        self.n_local = np.zeros(P, np.int32)
        for p in range(P):
            nl = self.loaders[p].num_nodes
            self.feats[p, :nl] = self.loaders[p].features
            self.labels[p, :nl] = self.loaders[p].labels
            self.n_local[p] = nl
        self.feats_j = self._per_machine(self.feats)
        self.labels_j = self._per_machine(self.labels)

        self.opt = make_optimizer(loc.optimizer, loc.lr)
        self.step = make_machine_step(model, self.opt)
        server_lr = srv.server_lr if srv.server_lr is not None else loc.lr
        self.server_opt = make_optimizer(loc.optimizer, server_lr)
        self.eval_fn = make_eval_fn(model)

        # full-graph full-neighbor operands for eval + correction: the
        # degree buckets, which every model reads; the padded table they
        # replace is a zero-width stand-in
        self.full_feats = jnp.asarray(data.features)
        self.full_labels = jnp.asarray(data.labels)
        self.full_table_j = jnp.zeros((data.num_nodes, 0), jnp.int32)
        self.full_mask_j = jnp.zeros((data.num_nodes, 0), jnp.float32)
        self.full_agg = bucketed_operands(data.graph)
        self.full_agg_stats = full_table_stats(data.graph)

        # correction-phase aggregation layout, resolved ONCE against the
        # full table's geometry (the correction regime IS the full-neighbor
        # regime the cost model targets; padded gathers the buckets'
        # slots); operands build lazily/at prewarm
        full_width = max(data.graph.max_degree(), 1)
        self.corr_agg_layout = choose_layout(
            srv.agg_layout, num_nodes=data.num_nodes,
            num_edges=data.graph.num_edges,
            width=full_width, full_width=full_width,
            sampled=srv.correction_sampling,
            padded_slots=self.full_agg_stats["full_agg_slots"])
        self._corr_agg = None

        params0 = model.init(plan.seed)
        self.param_bytes = tree_bytes(params0)
        # one machine's averaging payload on the wire (== param_bytes for
        # compression="none"; the compressed wire format otherwise)
        self.avg_payload_bytes = averaging_payload_bytes(
            params0, plan.comm.compression)
        self._halo_built = False

        # device-resident sampling (placement="device"): per-kind padded
        # CSR stacks + one jitted round sampler whose retraces we count —
        # static (num_steps, width, batch_size) means it compiles once per
        # K-bucket and kind, never per round
        self._device_key = jax.random.PRNGKey(plan.seed)
        self._device_csrs: Dict[str, DeviceCSR] = {}
        self._sampler_traces = TraceCounter()

        def _device_round(dcsr, key, num_steps, width, batch_size):
            # runs at trace time only; signature-aware so a resumed process
            # re-compiling a shape already traced pre-crash doesn't count
            self._sampler_traces.count(trace_signature(
                (dcsr, key), static=(num_steps, width, batch_size)))
            return sample_round_device(dcsr, key, num_steps, width,
                                       batch_size)

        self._device_round_jit = jax.jit(
            _device_round,
            static_argnames=("num_steps", "width", "batch_size"))

    def top_rows_stats(self) -> Dict:
        """The rows the loss reads, for which the forward computes the
        model's last ops alone (:meth:`~repro.models.gnn.model.GNNModel.
        apply` with ``rows``): per local step over all machines, per
        correction step and in the evaluation; and the op where the cut
        starts (:meth:`~repro.models.gnn.model.GNNModel.top_cut_op`), or
        ``"none"``."""
        cut = self.model.top_cut_op()
        return {"top_rows_local": self.num_machines * self.batch_size,
                "top_rows_correction": self.plan.server.server_batch_size,
                "top_rows_eval": len(self.data.val_nodes),
                "top_cut_op": "none" if cut is None else cut}

    def attention_stats(self) -> Dict:
        """GAT's engagement: slots its attention reads per round in the
        forward passes of the local phase (P·K steps; below the last layer
        every padded local row over ``fanout`` slots, at it the batch's
        rows) and of the server side (S correction steps and the
        evaluation; below the last layer over the degree buckets, at it the
        batch's rows at the widest bucket's width, or the validation rows'
        buckets), the node's own slot included with ``self_loop``; the XLA
        attention's chunk in bytes and whether the kernel runs.  Empty for
        other models."""
        model = self.model
        if model.arch != "GAT":
            return {}
        from repro.kernels.ops import ATTENTION_CHUNK_BYTES
        own = int(model.self_loop)

        def slots(buckets):
            return sum(t.shape[0] * (t.shape[1] + own)
                       for t in buckets.tables)

        below = model.num_layers - 1
        full = below * slots(self.full_agg.buckets)
        width = max(t.shape[1] for t in self.full_agg.buckets.tables) + own
        val = degree_buckets(self.data.graph, self.data.val_nodes)
        loc, srv = self.plan.local, self.plan.server
        local = below * self.n_max + self.batch_size
        corr = full + srv.server_batch_size * width
        return {"gat_local_slots": (self.num_machines * loc.local_k * local
                                    * (self.fanout + own)),
                "gat_server_slots": (srv.correction_steps * corr + full
                                     + slots(val)),
                "gat_chunk_bytes": ATTENTION_CHUNK_BYTES,
                "gat_kernel": bool(model.fused_gat)}

    @property
    def num_sampler_retraces(self) -> int:
        return self._sampler_traces.count_value

    def device_sampler_stats(self) -> Dict:
        """How the device sampler reads the sampled neighbor ids, for each
        round kind whose :class:`DeviceCSR` is built: ``sampler_select``
        (``"row_table"`` or ``"row_gather"``, chosen by the graph's max
        degree) and ``sampler_row_table_bytes``, the row tables' total;
        ``sampler_select`` is empty under host placement."""
        built = self._device_csrs
        return {"sampler_select": {k: d.select for k, d in built.items()},
                "sampler_row_table_bytes": sum(int(d.nbr.nbytes)
                                               for d in built.values())}

    def _per_machine(self, x) -> jnp.ndarray:
        """``x`` (leading machine axis) on the device.  With a mesh, one
        machine's slice per device of its ``('machine',)`` axis, so no
        round re-shards it from a single device."""
        if self._machine_sharding is None:
            return jnp.asarray(x)
        return jax.device_put(x, self._machine_sharding)

    # ----------------------------------------------------------- rng snapshot
    def snapshot(self) -> Dict:
        """JSON-able position of every host RNG stream (for exact resume).

        Three stream families feed a round: the ONE shared rng (minibatches,
        correction draws, ext tables), the per-loader neighbor-table rngs,
        and the server's full-neighbor sampler rng.  The device-placement
        key stream is stateless (``fold_in(PRNGKey(seed), r)``) and needs no
        snapshot; its retrace signatures do, so counts survive resume.
        """
        gen = lambda g: g.bit_generator.state
        return {"rng": gen(self.rng),
                "loader_rngs": [gen(ld.sampler._rng) for ld in self.loaders],
                "server_rng": gen(self.server_sampler._rng),
                "sampler_traces": self._sampler_traces.snapshot()}

    def restore_snapshot(self, snap: Dict) -> None:
        self.rng.bit_generator.state = snap["rng"]
        loader_states = snap["loader_rngs"]
        if len(loader_states) != len(self.loaders):
            raise ValueError(
                f"checkpoint has {len(loader_states)} loader RNG streams, "
                f"this plan has {len(self.loaders)} machines")
        for ld, s in zip(self.loaders, loader_states):
            ld.sampler._rng.bit_generator.state = s
        self.server_sampler._rng.bit_generator.state = snap["server_rng"]
        self._sampler_traces.restore(snap["sampler_traces"])

    # ------------------------------------------------------- device sampling
    def _device_csr(self, kind: str) -> DeviceCSR:
        """The kind's stacked :class:`DeviceCSR`, built once and cached."""
        dcsr = self._device_csrs.get(kind)
        if dcsr is not None:
            return dcsr
        sharding = self._machine_sharding
        if kind == "local":
            dcsr = build_device_csr(
                [ld.sampler.graph for ld in self.loaders], n_pad=self.n_max,
                train_nodes=[ld.train_nodes for ld in self.loaders],
                fanouts=[ld.sampler.fanout for ld in self.loaders],
                t_pad_min=self.batch_size, sharding=sharding)
        elif kind == "ext":
            self.ensure_halo()
            dcsr = build_device_csr(
                list(self.halo_plan.ext_graphs), n_pad=self.n_ext_max,
                train_nodes=[ld.train_nodes for ld in self.loaders],
                fanouts=[self.fanout_ext] * self.num_machines,
                t_pad_min=self.batch_size, sharding=sharding)
        elif kind == "full":
            dcsr = build_device_csr(
                [self.data.graph], n_pad=self.data.num_nodes,
                train_nodes=[self.data.train_nodes],
                fanouts=[self.fanout], t_pad_min=self.batch_size,
                sharding=sharding)
        else:
            raise ValueError(f"unknown round kind {kind!r}")
        self._device_csrs[kind] = dcsr
        return dcsr

    def _round_width(self, kind: str) -> int:
        return self.fanout_ext if kind == "ext" else self.fanout

    def prewarm(self, kinds, correction: bool = False) -> None:
        """Build every per-(graph, fanout) sampling structure up front.

        Host placement: touch each shard graph's cached ``_SamplingPlan``
        (and the ext graphs' for halo kinds) so hybrid plans that switch
        programs mid-schedule — halo→LLCG — never re-pay plan construction
        at the switch round.  Device placement: build each kind's
        :class:`DeviceCSR` stack.  Skipped under ``rng_compat`` (the legacy
        per-step path never used the batched plans).  ``correction=True``
        additionally prebuilds the correction phase's aggregation-layout
        operands (edge lists / BCSR tiles) so no round pays the host-side
        build.
        """
        kinds = set(kinds)
        if correction:
            self.correction_operands()
        if self.placement == "device":
            for kind in kinds:
                self._device_csr(kind)
            return
        if self.rng_compat:
            return
        if "local" in kinds:
            for ld in self.loaders:
                _all_nodes_plan(ld.sampler.graph, ld.sampler.fanout)
        if "ext" in kinds:
            self.ensure_halo()
            for g in self.halo_plan.ext_graphs:
                _all_nodes_plan(g, self.fanout_ext)
        if "full" in kinds:
            _all_nodes_plan(self.data.graph, self.fanout)

    def sample_round_on_device(self, desc: RoundDesc,
                               k_pad: Optional[int] = None):
        """One round's (tables, masks, batches, bmasks, step_valid) drawn on
        device at the bucketed length (documented key stream: the per-round
        key is ``fold_in(PRNGKey(seed), r)``; padded steps are real draws
        of later step indices, flagged invalid via ``step_valid``)."""
        k = desc.k if k_pad is None else k_pad
        dcsr = self._device_csr(desc.kind)
        key_r = jax.random.fold_in(self._device_key, desc.r)
        tables, masks, batches, bmasks = self._device_round_jit(
            dcsr, key_r, num_steps=k, width=self._round_width(desc.kind),
            batch_size=self.batch_size)
        svalid = None
        if k_pad is not None:
            svalid = jnp.concatenate(
                [jnp.ones((desc.k,), jnp.float32),
                 jnp.zeros((k_pad - desc.k,), jnp.float32)])
        return tables, masks, batches, bmasks, svalid

    # ------------------------------------------------------------- halo view
    def ensure_halo(self) -> None:
        """Build the extended-graph (local ∪ halo) machinery once.

        Deterministic — consumes no host RNG, so building it lazily leaves
        every sampling stream untouched (plans without halo rounds draw the
        exact same sequences whether or not this ever runs).
        """
        if self._halo_built:
            return
        data, P = self.data, self.num_machines
        self.halo_plan = build_halo_plan(data.graph, self.partition)
        self.n_ext_max = max(g.num_nodes for g in self.halo_plan.ext_graphs)
        self.halo_program = build_halo_program(data.graph, self.partition,
                                               plan=self.halo_plan,
                                               n_ext_pad=self.n_ext_max)
        self.fanout_ext = ext_fanout(self.halo_plan, self.fanout)
        d = data.feature_dim

        # padded extended features: local rows always; halo rows fetched
        # from global X host-side (host_halo) or left zero for the on-device
        # exchange to fill (engine-executed)
        self.ext_feats = np.zeros((P, self.n_ext_max, d), np.float32)
        self.local_feats = np.zeros((P, self.n_ext_max, d), np.float32)
        self.ext_labels = np.zeros((P, self.n_ext_max), np.int32)
        for p in range(P):
            local = self.partition.part_nodes[p]
            rows = np.concatenate([local, self.halo_plan.halo_nodes[p]]
                                  ).astype(np.int64)
            self.ext_feats[p, : rows.size] = data.features[rows]
            self.ext_labels[p, : rows.size] = data.labels[rows]
            self.local_feats[p, : local.size] = data.features[local]
        fdtype = self.ext_feats.dtype
        halo_comp = self.plan.comm.halo_compression
        self.halo_bytes_per_step = self.halo_program.halo_bytes(
            d, dtype=fdtype, compression=halo_comp)
        self.exchange_bytes_per_step = self.halo_program.exchange_bytes(
            d, dtype=fdtype, compression=halo_comp)
        self.halo_inputs = dict(
            halo_send_idx=self._per_machine(self.halo_program.send_idx),
            halo_recv_idx=self._per_machine(self.halo_program.recv_idx),
            halo_dest_idx=self._per_machine(self.halo_program.dest_idx),
            halo_recv_valid=self._per_machine(self.halo_program.recv_valid))
        self._halo_built = True

    # ---------------------------------------------------------------- local
    def local_batch(self, p: int):
        tn = self.loaders[p].train_nodes
        B = self.batch_size
        batch = sample_minibatch(tn, B, self.rng).astype(np.int32)
        bmask = _f32_mask(B)
        return batch, bmask

    # --------------------------------------------------------------- server
    def correction_operands(self):
        """The correction forward's prebuilt :class:`~repro.models.gnn.agg.
        AggOperands`, cached on the graph: the degree buckets for the padded
        layout on the full table (None for the per-step sampled tables of
        sampling-at-correction)."""
        if self.corr_agg_layout == "padded":
            if self.plan.server.correction_sampling:
                return None
            return self.full_agg
        if self._corr_agg is None:
            self._corr_agg = build_agg_operands(self.data.graph,
                                                self.corr_agg_layout)
        return self._corr_agg

    def correction_pool(self) -> np.ndarray:
        """Train-node pool for the server batch (Eq. 2 / App. A.3)."""
        if self.plan.server.max_cut_minibatch:
            src, dst = self.data.graph.to_edges()
            asg = self.partition.assignment
            cut_nodes = np.unique(np.concatenate(
                [src[asg[src] != asg[dst]], dst[asg[src] != asg[dst]]]))
            pool = np.intersect1d(cut_nodes, self.data.train_nodes)
            if pool.size:
                return pool
        return self.data.train_nodes

    def sample_correction(self) -> Dict:
        """S stacked server batches (+ per-step sampled tables if ablated)."""
        srv = self.plan.server
        S, Bs = srv.correction_steps, srv.server_batch_size
        pool = self.correction_pool()
        batches = np.zeros((S, Bs), np.int32)
        corr_tables, corr_masks = self.full_table_j, self.full_mask_j
        if srv.correction_sampling:
            if self.rng_compat:
                tabs = np.zeros((S, self.data.num_nodes, self.fanout),
                                np.int32)
                msks = _f32_mask(tabs.shape, 0.0)
                for s in range(S):
                    batches[s] = sample_minibatch(pool, Bs, self.rng)
                    t, m = sample_neighbors(self.data.graph,
                                            np.arange(self.data.num_nodes),
                                            self.fanout, self.rng,
                                            rng_compat=True)
                    tabs[s], msks[s] = t, m
            else:
                batches[:] = sample_minibatch_batched(pool, Bs, S, self.rng)
                tabs, msks = sample_neighbors_batched(
                    self.data.graph, None, self.fanout, self.rng, num_steps=S)
            corr_tables, corr_masks = jnp.asarray(tabs), jnp.asarray(msks)
        elif self.rng_compat:
            for s in range(S):
                batches[s] = sample_minibatch(pool, Bs, self.rng)
        else:
            batches[:] = sample_minibatch_batched(pool, Bs, S, self.rng)
        return dict(corr_feats=self.full_feats, corr_labels=self.full_labels,
                    corr_tables=corr_tables, corr_masks=corr_masks,
                    corr_batches=jnp.asarray(batches),
                    corr_bmasks=jnp.asarray(_f32_mask((S, Bs))),
                    corr_agg=self.correction_operands())

    # --------------------------------------------------------- round kinds
    def sample_local_round(self, k: int):
        """(tables, masks, batches, bmasks) numpy stacks for a local round."""
        return sample_round(self.loaders, k, self.batch_size, self.n_max,
                            self.fanout, self.rng, rng_compat=self.rng_compat)

    def sample_ext_round(self, k: int):
        """One halo round's extended-graph tables + local batches (numpy)."""
        self.ensure_halo()
        P, B = self.num_machines, self.batch_size
        tables = np.zeros((P, k, self.n_ext_max, self.fanout_ext), np.int32)
        masks = _f32_mask((P, k, self.n_ext_max, self.fanout_ext), 0.0)
        batches = np.zeros((P, k, B), np.int32)
        if self.rng_compat:
            # step-major / machine-minor on the ONE shared rng — the exact
            # draw order of the pre-engine per-step loop
            for i in range(k):
                for p in range(P):
                    g = self.halo_plan.ext_graphs[p]
                    t, m = sample_neighbors(g, np.arange(g.num_nodes),
                                            self.fanout_ext, self.rng,
                                            rng_compat=True)
                    tables[p, i, : g.num_nodes, : t.shape[1]] = t
                    masks[p, i, : g.num_nodes, : m.shape[1]] = m
                    batches[p, i], _ = self.local_batch(p)
        else:
            for p in range(P):
                g = self.halo_plan.ext_graphs[p]
                t, m = sample_neighbors_batched(g, None, self.fanout_ext,
                                                self.rng, num_steps=k)
                tables[p, :, : g.num_nodes] = t
                masks[p, :, : g.num_nodes] = m
                batches[p] = sample_minibatch_batched(
                    self.loaders[p].train_nodes, B, k, self.rng)
        return tables, masks, batches

    def sample_full_round(self, k: int):
        """Centralized reference: sample the UNpartitioned graph (P=1)."""
        data, N, B = self.data, self.data.num_nodes, self.batch_size
        if self.rng_compat:
            tables = np.zeros((1, k, N, self.fanout), np.int32)
            masks = _f32_mask((1, k, N, self.fanout), 0.0)
            batches = np.zeros((1, k, B), np.int32)
            for i in range(k):
                t, m = sample_neighbors(data.graph, np.arange(N), self.fanout,
                                        self.rng, rng_compat=True)
                tables[0, i, :, : t.shape[1]] = t
                masks[0, i, :, : m.shape[1]] = m
                batches[0, i] = sample_minibatch(data.train_nodes, B,
                                                 self.rng)
        else:
            t, m = sample_neighbors_batched(data.graph, None, self.fanout,
                                            self.rng, num_steps=k)
            tables, masks = t[None], m[None]
            batches = sample_minibatch_batched(
                data.train_nodes, B, k, self.rng)[None].astype(np.int32)
        return tables, masks, batches

    # ------------------------------------------------------------- dispatch
    def sample(self, desc: RoundDesc,
               k_pad: Optional[int] = None) -> RoundInputs:
        """One round's :class:`RoundInputs` for any lowered round kind.

        Host placement: draw order per round matches the legacy strategies
        exactly — local (or ext/full) tables+batches first, then — only on
        rounds where the correction phase is active — the server batches.
        Device placement: the round draw is ONE asynchronous jit dispatch
        (``k_pad`` draws directly at the bucketed length with the real
        prefix flagged in ``step_valid``); the correction batches stay
        host-drawn from the shared rng, so toggling placement never
        perturbs the server stream.
        """
        P, B = self.num_machines, self.batch_size
        svalid = None
        if self.placement == "device":
            tables, masks, batches, bmasks, svalid = \
                self.sample_round_on_device(desc, k_pad)
        elif desc.kind == "local":
            tables, masks, batches, bmasks = self.sample_local_round(desc.k)
        elif desc.kind == "ext":
            tables, masks, batches = self.sample_ext_round(desc.k)
            bmasks = _f32_mask((P, desc.k, B))
        elif desc.kind == "full":
            tables, masks, batches = self.sample_full_round(desc.k)
            bmasks = _f32_mask((1, desc.k, B))
        else:
            raise ValueError(f"unknown round kind {desc.kind!r}")
        corr = {}
        if desc.correction:
            with span("correction_draw"):
                corr = self.sample_correction()
        halo = {}
        if desc.kind == "ext" and desc.mode == "halo":
            halo = self.halo_inputs
        return RoundInputs(tables=self._per_machine(tables),
                           masks=self._per_machine(masks),
                           batches=self._per_machine(batches),
                           bmasks=self._per_machine(bmasks),
                           step_valid=svalid, **corr, **halo)

    def round_feats_labels(self, kind: str) -> Tuple[Any, Any]:
        """The (feats, labels) device arrays a round kind trains on."""
        if kind == "local":
            return self.feats_j, self.labels_j
        if kind == "ext":
            self.ensure_halo()
            feats = (self.ext_feats if self.plan.comm.host_halo
                     else self.local_feats)
            return self._per_machine(feats), self._per_machine(
                self.ext_labels)
        if kind == "full":
            return self.full_feats[None], self.full_labels[None]
        raise ValueError(f"unknown round kind {kind!r}")

    def evaluate(self, params, nodes):
        """Loss and score over ``nodes``, whose own slots are built once
        and cached on the graph (:func:`repro.models.gnn.agg.
        degree_buckets`)."""
        loss, score = self.eval_fn(params, self.full_feats, self.full_table_j,
                                   self.full_mask_j, self.full_labels,
                                   degree_buckets(self.data.graph, nodes),
                                   self.full_agg)
        with span("read"):
            return float(loss), float(score)

    def cut_stats(self) -> Dict:
        from repro.graph.partition import cut_edge_stats
        return cut_edge_stats(self.data.graph, self.partition.assignment)


# --------------------------------------------------------------------------
# Plan program — per-round dispatch over the engine's RoundPrograms
# --------------------------------------------------------------------------
class _PlanProgram:
    """Duck-typed ``RoundProgram`` that dispatches each round to the right
    engine program and threads the mixed optimizer state.

    ``run_schedule`` threads ONE (program, state) pair; a plan can mix round
    modes, so this facade keeps one :class:`RoundProgram` per distinct
    ``(mode, reset_opt)`` key, one persistent sub-state per program (local
    rounds carry their placeholder/stacked state, halo/sync rounds their
    per-step optimizer moments), and ONE shared server-optimizer state
    injected into whichever program runs a correction round.  The round
    cursor advances once per ``run_round`` call — exactly ``run_schedule``'s
    iteration order.  ``feats``/``labels`` passed by the driver are ignored;
    each round trains on its own kind's arrays from the sampler.
    """

    def __init__(self, model, sampler: RoundSampler,
                 descs: List[RoundDesc], backend: str, mesh=None):
        plan = sampler.plan
        self.descs = descs
        self.sampler = sampler
        self.with_correction = any(d.correction for d in descs)
        self.server_opt: Optional[Optimizer] = (
            sampler.server_opt if self.with_correction else None)
        # correction machinery is built only into program keys that
        # actually run a correction round (a hybrid plan's halo program
        # carries no server-optimizer state it would never use)
        corr_keys = {d.program_key for d in descs if d.correction}
        self.programs: Dict[Tuple, RoundProgram] = {}
        for key in {d.program_key for d in descs}:
            mode, reset = key
            self.programs[key] = RoundProgram(
                model, sampler.opt,
                self.server_opt if key in corr_keys else None,
                EngineConfig(num_machines=plan.comm.num_machines,
                             mode=mode, backend=backend,
                             with_correction=key in corr_keys,
                             reset_local_opt=(reset if mode == "local"
                                              else True),
                             compression=plan.comm.compression,
                             halo_compression=plan.comm.halo_compression,
                             comm_seed=plan.seed),
                mesh=mesh)
        self._data = {kind: sampler.round_feats_labels(kind)
                      for kind in {d.kind for d in descs}}
        self._cursor = 0
        self._sub: Dict[Tuple, EngineState] = {}
        self._server_state = None
        self._key_by_str = {self._key_str(k): k for k in self.programs}

    @staticmethod
    def _key_str(key: Tuple) -> str:
        """Program key as a stable JSON-able string (checkpoint tree keys)."""
        mode, reset = key
        return f"{mode}:{reset}"

    @property
    def num_retraces(self) -> int:
        return sum(p.num_retraces for p in self.programs.values())

    @property
    def num_corr_retraces(self) -> int:
        return sum(p.num_corr_retraces for p in self.programs.values())

    # --------------------------------------------------- checkpoint snapshot
    def snapshot_state(self, state: EngineState) -> Dict:
        """The FULL mutable array state as one pytree (for the manager).

        Covers the global params, the shared server-optimizer state, and
        every per-program sub-state's optimizer moments + error-feedback
        residual.  Sub-state ``params``/``server_opt_state`` are excluded —
        both are re-injected from the outer state on every ``run_round``.
        Call :meth:`init_state` first to build the same tree as a restore
        template.
        """
        return {"params": state.params,
                "server": self._server_state,
                "subs": {self._key_str(k): {"opt": s.local_opt_state,
                                            "residual": s.comm_residual}
                         for k, s in self._sub.items()}}

    def train_state(self) -> Dict:
        """JSON-able non-array position: cursor + per-program trace state."""
        return {"cursor": self._cursor,
                "programs": {self._key_str(k): p.trace_state()
                             for k, p in self.programs.items()}}

    def restore_run_state(self, tree: Dict, aux: Dict) -> EngineState:
        """Rehydrate from a checkpoint; returns the outer EngineState.

        ``tree`` is a restored :meth:`snapshot_state` pytree, ``aux`` the
        matching :meth:`train_state` payload.  Must run after
        :meth:`init_state` (which built ``_sub`` as the restore template).
        """
        to_dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        params = to_dev(tree["params"])
        self._cursor = int(aux["cursor"])
        for ks, snap in aux["programs"].items():
            key = self._key_by_str.get(ks)
            if key is None:
                raise ValueError(f"checkpoint carries engine program {ks!r} "
                                 "this plan does not lower")
            self.programs[key].restore_trace_state(snap)
        if self.with_correction:
            self._server_state = to_dev(tree["server"])
        for key in self.programs:
            sub_t = tree["subs"][self._key_str(key)]
            res = sub_t["residual"]
            self._sub[key] = EngineState(
                params=params,
                local_opt_state=to_dev(sub_t["opt"]),
                server_opt_state=None,
                comm_residual=None if res is None else to_dev(res))
        return EngineState(params=params, local_opt_state=jnp.zeros(()))

    def init_state(self, params) -> EngineState:
        self._cursor = 0
        self._sub = {k: p.init_state(params)
                     for k, p in self.programs.items()}
        if self.with_correction:
            self._server_state = self.server_opt.init(params)
        return EngineState(params=params, local_opt_state=jnp.zeros(()))

    def run_round(self, state: EngineState, feats, labels,
                  inputs: RoundInputs):
        desc = self.descs[self._cursor]
        self._cursor += 1
        prog = self.programs[desc.program_key]
        sub = self._sub[desc.program_key]
        corr = prog.cfg.with_correction
        sub = EngineState(params=state.params,
                          local_opt_state=sub.local_opt_state,
                          server_opt_state=(self._server_state if corr
                                            else None),
                          comm_residual=sub.comm_residual)
        feats, labels = self._data[desc.kind]
        new, metrics = prog.run_round(sub, feats, labels, inputs)
        self._sub[desc.program_key] = new
        if corr:
            self._server_state = new.server_opt_state
        return EngineState(params=new.params,
                           local_opt_state=state.local_opt_state), metrics


# --------------------------------------------------------------------------
# checkpoint identity + the run_schedule checkpoint hook
# --------------------------------------------------------------------------
def plan_digest_of(plan: TrainPlan, backend: str) -> str:
    """Digest of everything that shapes the trajectory (for resume refusal).

    Covers the plan description, the backend, and the resolved schedule —
    but NOT the checkpoint spec itself: changing where/how often snapshots
    land (or resuming with checkpointing off) does not change the math, so
    it must not invalidate existing checkpoints.
    """
    desc = plan.describe()
    desc.pop("checkpoint", None)
    return digest_json({"plan": desc, "backend": backend,
                        "schedule": plan.schedule.resolve(plan.local.local_k)})


def dataset_digest(data: SyntheticDataset) -> str:
    """Content digest of the dataset a checkpoint was trained on."""
    src, dst = data.graph.to_edges()
    h = hashlib.sha256()
    for arr in (data.features, data.labels, data.train_nodes,
                data.val_nodes, src, dst):
        h.update(np.ascontiguousarray(arr).tobytes())
    return digest_json({"num_nodes": int(data.num_nodes),
                        "num_edges": int(data.graph.num_edges),
                        "payload": h.hexdigest()})


class _PlanCheckpointHook:
    """Two-phase checkpoint tap ``run_schedule`` drives on every round.

    ``after_round(r)`` — fired right after round r's dispatch, BEFORE the
    prefetched round-r+1 sample — snapshots the host RNG streams at exactly
    "rounds 1..r drawn".  ``commit(r)`` — fired once round r's History rows
    land — pairs that snapshot with the array state and hands both to the
    async manager.  Rounds where ``r % every != 0`` skip both phases.
    """

    def __init__(self, manager: CheckpointManager, sampler: RoundSampler,
                 program: "_PlanProgram", every: int,
                 plan_digest: str, data_digest: str):
        self.manager = manager
        self.sampler = sampler
        self.program = program
        self.every = every
        self.plan_digest = plan_digest
        self.data_digest = data_digest
        self._rng_snap: Optional[Dict] = None

    def _due(self, r: int) -> bool:
        return r % self.every == 0

    def after_round(self, r: int, state: EngineState) -> None:
        if self._due(r):
            self._rng_snap = self.sampler.snapshot()

    def commit(self, r: int, state: EngineState, hist: History) -> None:
        if not self._due(r):
            return
        train = {"round": r,
                 "sampler": self._rng_snap,
                 "program": self.program.train_state(),
                 "history": hist.to_json()}
        self.manager.save(r, self.program.snapshot_state(state), train=train,
                          plan_digest=self.plan_digest,
                          data_digest=self.data_digest)
        self._rng_snap = None


# --------------------------------------------------------------------------
# build_trainer — the one entry point
# --------------------------------------------------------------------------
class PlanTrainer:
    """A lowered :class:`TrainPlan`, ready to run.

    Construction validates and lowers the plan (:func:`lower_plan`) —
    composition errors surface immediately.  :meth:`run` builds the
    :class:`RoundSampler`, the engine programs and the schedule driver
    fresh on every call, so repeated runs reproduce identical trajectories
    (the RNG streams restart), exactly like the legacy ``run_*`` entry
    points did.
    """

    def __init__(self, data: SyntheticDataset, model: GNNModel,
                 plan: TrainPlan, backend: str = "vmap", mesh=None):
        _check(backend in BACKENDS,
               f"unknown backend {backend!r}; choose one of {BACKENDS}")
        if backend == "shard_map" and mesh is None:
            raise ValueError("backend='shard_map' requires a mesh with a "
                             "'machine' axis")
        self.data, self.model, self.plan = data, model, plan
        self.backend, self.mesh = backend, mesh
        self.descs = lower_plan(plan)
        self.schedule = [d.k for d in self.descs]

    # ------------------------------------------------------------ accounting
    def accounting(self, sampler: Optional[RoundSampler] = None
                   ) -> List[Dict]:
        """Per-round (kind, bytes, steps) without running any training.

        Builds a :class:`RoundSampler` (for the halo byte model) unless one
        is passed; device programs are never compiled.
        """
        if sampler is None:
            sampler = RoundSampler(self.data, self.model, self.plan)
        P, pb = self.plan.comm.num_machines, sampler.param_bytes
        apb = sampler.avg_payload_bytes
        rows = []
        for d in self.descs:
            if d.kind == "ext":
                sampler.ensure_halo()
                comm_step = (sampler.halo_bytes_per_step
                             if self.plan.comm.host_halo
                             else sampler.exchange_bytes_per_step)
                # the per-step grad pmean stays full f32 (only averaging
                # deltas and halo features are compressed)
                nbytes = d.k * (comm_step + 2 * P * pb)
            elif d.kind == "local" and d.averaging:
                # up + down per machine, charged whenever the averaging
                # phase runs — including P=1, exactly as the legacy
                # periodic strategies accounted it (drop the averaging
                # phase, as the single-machine plan does, to charge 0).
                # Priced at the compressed wire format (== param_bytes
                # when compression="none").
                nbytes = 2.0 * P * apb
            else:
                nbytes = 0.0
            rows.append({"round": d.r, "k": d.k, "kind": d.kind,
                         "mode": d.mode, "correction": d.correction,
                         "bytes": nbytes, "steps": P * d.k})
        return rows

    # ------------------------------------------------------------------- run
    def run(self, resume_from: Optional[str] = None,
            resume_step: Optional[int] = None) -> History:
        """Run the plan; ``resume_from`` continues a checkpointed run.

        ``resume_from`` names a :class:`CheckpointSpec` directory; the
        latest VALID checkpoint (or ``resume_step``) is restored — params,
        optimizer states, comm residual, RNG streams, cursor, retrace
        signatures, History — and training continues mid-schedule,
        bit-identical to the uninterrupted run.  Checkpoints whose plan or
        dataset digest mismatches this trainer are refused.
        """
        plan, data, model = self.plan, self.data, self.model
        # deliberately locals, not attributes: a finished trainer must not
        # pin the padded feature copies + jit caches in memory (sweeps hold
        # many PlanTrainer objects)
        sampler = RoundSampler(data, model, plan, mesh=self.mesh)
        if any(d.kind == "ext" for d in self.descs):
            sampler.ensure_halo()
        sampler.prewarm({d.kind for d in self.descs},
                        correction=any(d.correction for d in self.descs))
        program = _PlanProgram(model, sampler, self.descs, self.backend,
                               self.mesh)
        acct = self.accounting(sampler)
        by_round = {row["round"]: row for row in acct}
        bucketing = plan.compile.bucketing_for(self.schedule,
                                               plan.local.local_k)

        meta: Dict = {"param_bytes": sampler.param_bytes,
                      "plan": plan.describe(),
                      "sampler_placement": sampler.placement,
                      "sampler_overlap": plan.sampler.resolved_overlap,
                      **sampler.device_sampler_stats(),
                      "corr_agg_layout": sampler.corr_agg_layout,
                      **sampler.full_agg_stats, **sampler.top_rows_stats(),
                      **sampler.attention_stats()}
        if any(d.kind == "ext" for d in self.descs):
            meta.update({
                "halo_executed": not plan.comm.host_halo,
                "halo_bytes_per_step": sampler.halo_bytes_per_step,
                "exchange_bytes_per_step": sampler.exchange_bytes_per_step,
                "halo_max_send": sampler.halo_program.max_send,
                "halo_max_halo": sampler.halo_program.max_halo})

        desc_by_round = {d.r: d for d in self.descs}
        if sampler.placement == "device" and bucketing is not None:
            # draw directly at the bucketed length (step_valid marks the
            # real prefix) — same compiled sampler per bucket, zero host pad
            def sample_fn(r, k):
                return sampler.sample(desc_by_round[r],
                                      k_pad=bucketing.pad_length(k))
        else:
            def sample_fn(r, k):
                return sampler.sample(desc_by_round[r])
        mesh_ctx = (self.mesh if self.backend == "shard_map"
                    else contextlib.nullcontext())

        pdig = plan_digest_of(plan, self.backend)
        ddig = dataset_digest(data)
        resume = None
        if resume_from is not None:
            resume = self._restore(resume_from, resume_step, program,
                                   model.init(plan.seed), pdig, ddig)
        manager = hook = None
        if plan.checkpoint is not None:
            ck = plan.checkpoint
            manager = CheckpointManager(ck.dir, keep=ck.keep,
                                        async_=ck.async_,
                                        queue_size=ck.queue_size)
            hook = _PlanCheckpointHook(manager, sampler, program, ck.every,
                                       pdig, ddig)
        try:
            with mesh_ctx:
                hist = run_schedule(
                    program, model.init(plan.seed), None, None,
                    sample_fn,
                    self.schedule,
                    lambda p: sampler.evaluate(p, data.val_nodes),
                    plan.name,
                    bytes_per_round=lambda r, k: by_round[r]["bytes"],
                    steps_per_round=lambda r, k: by_round[r]["steps"],
                    meta=meta,
                    bucketing=bucketing,
                    checkpoint_dir=plan.checkpoint_dir,
                    prefetch=plan.sampler.resolved_overlap,
                    checkpoint_hook=hook,
                    resume=resume)
        finally:
            if manager is not None:
                manager.close()
        hist.meta["cut_stats"] = sampler.cut_stats()
        hist.meta["round_kinds"] = [d.kind for d in self.descs]
        hist.meta["sampler_retraces"] = sampler.num_sampler_retraces
        return hist

    def _restore(self, resume_from: str, resume_step: Optional[int],
                 program: _PlanProgram, params0, pdig: str,
                 ddig: str) -> ResumePoint:
        """Load the latest valid (or explicit) checkpoint into ``program``.

        The restore template is the freshly-initialized program state —
        exact tree structure, shapes and dtypes for every leaf — so a
        checkpoint from a different architecture or compression codec fails
        shape/dtype checks instead of restoring garbage; digests catch
        everything subtler.  ``program``'s sampler must not have consumed
        any RNG yet (its streams are overwritten wholesale).
        """
        from repro.checkpoint.manager import CheckpointRefused

        def check_identity(manifest):
            if manifest.get("plan_digest") != pdig:
                raise CheckpointRefused(
                    f"checkpoint under {resume_from} was written by a "
                    "different plan/backend (plan digest mismatch); refusing "
                    "to resume — a silent divergence is worse than a restart")
            if manifest.get("data_digest") != ddig:
                raise CheckpointRefused(
                    f"checkpoint under {resume_from} was trained on "
                    "different data (dataset digest mismatch); refusing to "
                    "resume")

        reader = CheckpointManager(resume_from, keep=0, async_=False)
        template = program.snapshot_state(program.init_state(params0))
        tree, manifest = reader.restore(template, step=resume_step,
                                        manifest_check=check_identity)
        train = manifest["train"]
        state0 = program.restore_run_state(tree, train["program"])
        program.sampler.restore_snapshot(train["sampler"])
        return ResumePoint(state=state0,
                           history=History.from_json(train["history"]),
                           start_round=int(train["round"]) + 1)


def build_trainer(data: SyntheticDataset, model: GNNModel, plan: TrainPlan,
                  backend: str = "vmap", mesh=None) -> PlanTrainer:
    """Lower ``plan`` onto the round engine; run with ``.run() -> History``.

    ``backend="vmap"`` simulates the machine axis on any host;
    ``backend="shard_map"`` binds one device per machine over the given
    mesh's ``('machine',)`` axis (the production path).  Both execute the
    same per-machine round bodies and agree numerically.
    """
    return PlanTrainer(data, model, plan, backend=backend, mesh=mesh)


# --------------------------------------------------------------------------
# DistConfig — the legacy flat config, now a validated deprecation shim
# --------------------------------------------------------------------------
@dataclasses.dataclass
class DistConfig:
    """Flat legacy config (deprecated — compose a :class:`TrainPlan`).

    Still accepted everywhere for compatibility; every field is validated
    at construction and :meth:`specs` regroups them into the typed
    sub-configs the plan API takes.
    """

    num_machines: int = 8
    rounds: int = 20
    local_k: int = 4                 # K
    rho: float = 1.0                 # ρ  (>1 → LLCG schedule; 1.0 → PSGD-PA)
    correction_steps: int = 1        # S
    batch_size: int = 32             # B_L
    server_batch_size: int = 64      # B_S
    fanout: Optional[int] = 10       # neighbor-sampling fanout (None = full)
    fanout_ratio: Optional[float] = None
    lr: float = 1e-2                 # η
    server_lr: Optional[float] = None  # γ (defaults to η)
    optimizer: str = "adam"          # paper uses ADAM (App. A.2)
    partition_method: str = "bfs"
    correction_sampling: bool = False  # App. A "sampling at correction"
    max_cut_minibatch: bool = False    # App. A.3 ablation
    server_agg_layout: str = "padded"  # correction-forward agg layout
    rng_compat: bool = False         # replay the pre-vectorization RNG
    k_bucketing: bool = False        # pad K to buckets → O(log) retraces
    bucket_growth: int = 2           # bucket lengths are local_k·growth^i
    bucket_mode: str = "geometric"   # "geometric" | "fit" (schedule-aware)
    ggs_host_halo: bool = False      # legacy GGS: host-materialized halo
    checkpoint_dir: Optional[str] = None  # params-export (train→serve hook)
    seed: int = 0

    def __post_init__(self):
        # constructing the grouped specs IS the validation: every allowed
        # value lives in exactly one place and errors fire here, not three
        # layers into a run
        self.specs()

    def specs(self) -> Dict[str, Any]:
        """Regroup into the TrainPlan sub-configs (validates all fields)."""
        return dict(
            local=LocalSpec(local_k=self.local_k, batch_size=self.batch_size,
                            lr=self.lr, optimizer=self.optimizer),
            server=ServerSpec(correction_steps=self.correction_steps,
                              server_batch_size=self.server_batch_size,
                              server_lr=self.server_lr,
                              correction_sampling=self.correction_sampling,
                              max_cut_minibatch=self.max_cut_minibatch,
                              agg_layout=self.server_agg_layout),
            comm=CommSpec(num_machines=self.num_machines,
                          partition_method=self.partition_method,
                          host_halo=self.ggs_host_halo),
            sampler=SamplerSpec(fanout=self.fanout,
                                fanout_ratio=self.fanout_ratio),
            schedule=ScheduleSpec(rounds=self.rounds, rho=self.rho),
            compile=CompileSpec(rng_compat=self.rng_compat,
                                k_bucketing=self.k_bucketing,
                                bucket_growth=self.bucket_growth,
                                bucket_mode=self.bucket_mode),
        )


# --------------------------------------------------------------------------
# Canned plans — the paper's strategies as one-line compositions
# --------------------------------------------------------------------------
def _plan(cfg: DistConfig, phases: Tuple[RoundPhase, ...], name: str,
          **overrides) -> TrainPlan:
    specs = cfg.specs()
    specs.update(overrides)
    return TrainPlan(phases=phases, name=name, seed=cfg.seed,
                     checkpoint_dir=cfg.checkpoint_dir, **specs)


def psgd_pa_plan(cfg: DistConfig) -> TrainPlan:
    """Algorithm 1 — K local steps + parameter averaging, fixed schedule."""
    cfg = dataclasses.replace(cfg, rho=1.0)
    return _plan(cfg, (local_steps(), averaging()), "psgd_pa")


def llcg_plan(cfg: DistConfig, correction_every: int = 1) -> TrainPlan:
    """Algorithm 2 — PSGD-PA + the global server correction.

    ``correction_every=m`` runs the correction only on every m-th round —
    one of the compositions the legacy API could not express.
    """
    return _plan(cfg, (local_steps(), averaging(),
                       correction(every=correction_every)), "llcg")


def ggs_plan(cfg: DistConfig) -> TrainPlan:
    """GGS baseline — per-step halo exchange + per-step averaging."""
    return _plan(cfg, (halo_exchange(),), "ggs",
                 schedule=ScheduleSpec(rounds=cfg.rounds, rho=1.0))


def single_machine_plan(cfg: DistConfig) -> TrainPlan:
    """Centralized full-graph reference (Figure 4's dashed baseline)."""
    specs = cfg.specs()
    return _plan(cfg, (local_steps(reset_opt=False),), "single",
                 comm=CommSpec(num_machines=1, partition_method="random"),
                 sampler=dataclasses.replace(specs["sampler"],
                                             full_graph=True),
                 schedule=ScheduleSpec(rounds=cfg.rounds, rho=1.0))
