import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combination.

For each case this driver:
  1. builds the production mesh (16×16 single-pod or 2×16×16 multi-pod),
  2. constructs the step function for the shape kind
       train_4k    → the LLCG round step (K local steps + grouped parameter
                     averaging + S server corrections) — the paper's
                     technique as one lowered program; optionally the
                     fully-synchronous baseline (--variant sync),
       prefill_32k → prefill forward,
       decode_*    → one-token serve_step against a sharded KV/SSM cache,
  3. lowers with ShapeDtypeStruct inputs carrying NamedShardings (no
     allocation anywhere), compiles, and
  4. records memory_analysis / cost_analysis / per-device collective bytes
     parsed from the partitioned HLO into a JSON blob for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import re
import sys
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import (
    ARCH_IDS, SHAPES, get_config, get_long_context_config, shape_supported,
    train_batch_specs, prefill_batch_specs,
)
from repro.distributed.sharding import (
    param_pspecs, batch_pspec, group_axis_for, _fix_divisibility,
)
from repro.distributed.steps import (
    LLCGStepConfig, build_llcg_round_step, build_sync_train_step,
    build_prefill_step, build_decode_step,
)
from repro.launch.mesh import make_production_mesh
from repro.models.transformer.model import LM
from repro.optim import adamw
from repro.utils.logging import get_logger

log = get_logger("dryrun")

# ---------------------------------------------------------------- hardware
PEAK_FLOPS = 197e12          # bf16 / chip (TPU v5e)
HBM_BW = 819e9               # B/s / chip
LINK_BW = 50e9               # B/s / ICI link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


_IOTA_RG_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
                         r"(?:T\(([0-9,]+)\))?")
_EXPL_RG_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


_PAIRS_RE = re.compile(r"source_target_pairs=\{\{(\d+),(\d+)\}")


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a flat dict (``{}`` when the backend
    reports none)."""
    return dict(compiled.cost_analysis() or {})


def _first_group(line: str):
    """First replica group's member ids, handling iota-v2, explicit, and
    collective-permute source_target_pairs forms."""
    m = _IOTA_RG_RE.search(line)
    if m:
        num_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        n = int(np.prod(dims))
        arr = np.arange(n).reshape(dims)
        if m.group(4):
            arr = arr.transpose([int(x) for x in m.group(4).split(",")])
        return arr.reshape(num_groups, group_size)[0]
    m = _EXPL_RG_RE.search(line)
    if m:
        return np.array([int(x) for x in m.group(1).split(",")])
    m = _PAIRS_RE.search(line)
    if m:
        return np.array([int(m.group(1)), int(m.group(2))])
    return None


def _classify_span(members, mesh_shape) -> str:
    """Which mesh axes a replica group spans ('model'/'data'/'pod'/mixes)."""
    coords = []
    shape = list(mesh_shape)  # e.g. (16,16) or (2,16,16), row-major device ids
    for dev in members:
        c, rest = [], int(dev)
        for s in reversed(shape):
            c.append(rest % s)
            rest //= s
        coords.append(tuple(reversed(c)))
    coords = np.array(coords)
    names = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
    spanned = [names[i] for i in range(len(shape))
               if len(np.unique(coords[:, i])) > 1]
    return "+".join(spanned) if spanned else "self"


def collective_bytes_from_hlo(hlo_text: str,
                              mesh_shape=(16, 16)) -> Dict[str, float]:
    """Per-device bytes by collective kind AND by mesh-axis span.

    The compiled module is the per-partition program, so result shapes are
    per-device; summing result bytes per op approximates the per-device
    traffic each step (all-reduce counted twice: reduce-scatter+all-gather).
    ``inter_group`` sums traffic that crosses the LLCG machine boundary
    (the pod axis on multi-pod, the data axis on single-pod) — the paper's
    communication cost; ``intra_group`` is fast tensor-parallel traffic.
    """
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    spans: Dict[str, float] = {}
    slow_axis = "pod" if len(mesh_shape) == 3 else "data"
    inter = intra = 0.0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.search(r"=\s+(\S+)\s+(all-reduce|all-gather|reduce-scatter|"
                      r"all-to-all|collective-permute)(?:-start)?\(", s)
        if not m:
            continue
        result_type, kind = m.group(1), m.group(2)
        size = 0.0
        for dt, dims in _SHAPE_RE.findall(result_type):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * _DTYPE_BYTES[dt]
        size *= 2.0 if kind == "all-reduce" else 1.0
        out[kind] += size
        members = _first_group(s)
        span = (_classify_span(members, mesh_shape)
                if members is not None else "unknown")
        spans[span] = spans.get(span, 0.0) + size
        if slow_axis in span:
            inter += size
        else:
            intra += size
    out["total"] = sum(v for k, v in out.items() if k in _COLLECTIVES)
    out["inter_group"] = inter
    out["intra_group"] = intra
    out["by_span"] = spans  # type: ignore[assignment]
    return out


# ---------------------------------------------------------------- case build
def _sds(tree, spec_tree, mesh):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    def one(x, s):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, s))
    return jax.tree_util.tree_map(one, tree, spec_tree,
                                  is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _state_pspecs(state_shapes, cfg, mesh) -> Any:
    """Sharding rules for decode caches/states."""
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    daxis = data_axes if len(data_axes) > 1 else data_axes[0]
    flat, treedef = jax.tree_util.tree_flatten_with_path(state_shapes)
    specs = []
    for path, leaf in flat:
        names = [getattr(p, "key", getattr(p, "name", getattr(p, "idx", "")))
                 for p in path]
        name = str(names[-1])
        nd = leaf.ndim
        if name in ("k", "v") and nd >= 4:
            # (..., B, L, kv, hd): batch→data, kv heads→model.  When kv
            # doesn't divide the model axis (GQA kv < 16), shard head_dim
            # instead — the q·k and p·v contractions stay shard-local with a
            # tiny psum, and it's what keeps a 32k×128 cache under HBM
            # (§Perf stablelm iteration C2: 43 GB → ~2.7 GB per device).
            kv_dim, hd_dim = leaf.shape[nd - 2], leaf.shape[nd - 1]
            msize = mesh.shape["model"]
            if kv_dim % msize == 0:
                spec = [None] * (nd - 4) + [daxis, None, "model", None]
            elif hd_dim % msize == 0:
                spec = [None] * (nd - 4) + [daxis, None, None, "model"]
            else:
                spec = [None] * (nd - 4) + [daxis, None, None, None]
        elif name == "h" and nd >= 3:
            # (..., B·H, dk, dv): fused batch·heads → (data, model) best effort
            spec = [None] * (nd - 3) + [tuple(data_axes) + ("model",), None, None]
        elif name == "conv" and nd >= 3:
            spec = [None] * (nd - 3) + [daxis, None, "model"]
        elif name in ("k_scale", "v_scale") and nd >= 3:
            # (..., B, L, kv) int8-cache scales: batch over data
            spec = [None] * (nd - 3) + [daxis, None, None]
        elif name in ("x_att", "x_ffn", "emb0_last") and nd >= 3:
            spec = [None] * (nd - 3) + [daxis, None, None]
        elif name == "pos":
            spec = [None] * nd
        else:
            spec = [None] * nd
        specs.append(P(*_fix_divisibility(tuple(spec), leaf.shape, mesh)))
    return jax.tree_util.tree_unflatten(treedef, specs)


@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh: str
    variant: str
    ok: bool
    error: Optional[str] = None
    lower_s: float = 0.0
    compile_s: float = 0.0
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective: Dict[str, float] = dataclasses.field(default_factory=dict)
    memory: Dict[str, float] = dataclasses.field(default_factory=dict)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


def build_case(arch: str, shape_name: str, mesh: Mesh, variant: str = "llcg",
               llcg_k: int = 2, llcg_s: int = 1, remat: bool = True,
               cfg_override=None, unroll: bool = False,
               expert_hint: bool = False, avg_bf16: bool = False,
               serve_params_dtype: str = "float32") -> Tuple[Any, tuple]:
    """Returns (jitted_fn, abstract_args) ready to .lower(*args)."""
    from repro.distributed.hints import set_hint
    set_hint("expert_axis", "model" if expert_hint else None)
    set_hint("expert_axis_size", mesh.shape["model"] if expert_hint else 0)
    shp = SHAPES[shape_name]
    cfg = cfg_override
    if cfg is None:
        cfg = (get_long_context_config(arch) if shape_name == "long_500k"
               else get_config(arch))
    model = LM(cfg, unroll=unroll)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    if shp.kind == "train":
        opt = adamw(1e-3)
        gaxis = group_axis_for(mesh)
        if variant == "sync":
            pspec = param_pspecs(params_shapes, cfg, mesh, group_axis=None)
            opt_shapes = jax.eval_shape(opt.init, params_shapes)
            opt_spec = type(opt_shapes)(step=P(), mu=pspec, nu=pspec)
            batch = train_batch_specs(cfg, shp.global_batch, shp.seq_len)
            bspec = jax.tree_util.tree_map(lambda _: batch_pspec(mesh), batch)
            step = build_sync_train_step(model, opt, remat=remat)
            args = (_sds(params_shapes, pspec, mesh),
                    _sds(opt_shapes, opt_spec, mesh),
                    _sds(batch, bspec, mesh))
            return jax.jit(step), args

        G = mesh.shape[gaxis]
        stack = lambda tree, n: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype), tree)
        params_G = stack(params_shapes, G)
        pspec_G = param_pspecs(params_shapes, cfg, mesh, group_axis=gaxis)
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        opt_G = stack(opt_shapes, G)
        opt_spec_G = type(opt_shapes)(step=P(gaxis), mu=pspec_G, nu=pspec_G)
        server_opt_shapes = jax.eval_shape(opt.init, params_shapes)
        pspec = param_pspecs(params_shapes, cfg, mesh, group_axis=None)
        server_spec = type(server_opt_shapes)(step=P(), mu=pspec, nu=pspec)

        b_local = shp.global_batch // G
        lb = train_batch_specs(cfg, b_local, shp.seq_len)
        local_batch = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((G, llcg_k) + x.shape, x.dtype), lb)
        lbspec = jax.tree_util.tree_map(
            lambda _: batch_pspec(mesh, stacked_group=True, extra_leading=1),
            lb)
        cb = train_batch_specs(cfg, shp.global_batch, shp.seq_len)
        corr_batch = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((llcg_s,) + x.shape, x.dtype), cb)
        cbspec = jax.tree_util.tree_map(
            lambda _: batch_pspec(mesh, extra_leading=1), cb)

        step = build_llcg_round_step(
            model, adamw(1e-3), adamw(5e-4),
            LLCGStepConfig(num_groups=G, local_steps=llcg_k,
                           correction_steps=llcg_s, remat=remat,
                           avg_bf16=avg_bf16))
        args = (_sds(params_G, pspec_G, mesh),
                _sds(opt_G, opt_spec_G, mesh),
                _sds(server_opt_shapes, server_spec, mesh),
                _sds(local_batch, lbspec, mesh),
                _sds(corr_batch, cbspec, mesh))
        return jax.jit(step), args

    pspec = param_pspecs(params_shapes, cfg, mesh, group_axis=None)
    if serve_params_dtype != "float32":
        # serving-weights precision (production norm: bf16 inference)
        params_shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, jnp.dtype(serve_params_dtype)), params_shapes)
    params_sds = _sds(params_shapes, pspec, mesh)

    if shp.kind == "prefill":
        batch = prefill_batch_specs(cfg, shp.global_batch, shp.seq_len)
        bspec = jax.tree_util.tree_map(lambda _: batch_pspec(mesh), batch)
        step = build_prefill_step(model, max_seq=shp.seq_len)
        return jax.jit(step), (params_sds, _sds(batch, bspec, mesh))

    # decode
    state_shapes = jax.eval_shape(
        lambda: model.init_states(None, shp.global_batch, shp.seq_len))
    sspec = _state_pspecs(state_shapes, cfg, mesh)
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tok = jax.ShapeDtypeStruct((shp.global_batch,), jnp.int32)
    tok_spec = P(daxes if len(daxes) > 1 else daxes[0]) \
        if shp.global_batch % np.prod([mesh.shape[a] for a in daxes]) == 0 else P()
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    step = build_decode_step(model, max_seq=shp.seq_len)
    args = (params_sds, _sds(state_shapes, sspec, mesh),
            jax.ShapeDtypeStruct(tok.shape, tok.dtype,
                                 sharding=NamedSharding(mesh, tok_spec)),
            jax.ShapeDtypeStruct(pos.shape, pos.dtype,
                                 sharding=NamedSharding(mesh, P())))
    return jax.jit(step), args


def build_gnn_engine_case(num_machines: int = 16, num_nodes: int = 4096,
                          feature_dim: int = 64, num_classes: int = 16,
                          hidden_dim: int = 64, local_k: int = 4,
                          batch_size: int = 64, fanout: int = 16,
                          mode: str = "local",
                          halo_compression: str = "none"):
    """Lower the unified GNN round program (shard_map backend) abstractly.

    Builds :class:`repro.core.engine.RoundProgram` on a virtual
    ``('machine',)`` mesh and returns ``(jitted_round, abstract_args, mesh,
    meta)`` ready to ``.lower(*args)`` — ShapeDtypeStruct inputs only, no
    feature data — so the dry-run can record the round's collective bytes.

    ``mode="local"`` lowers the LLCG local phase (one model all-reduce per
    round).  ``mode="halo"`` lowers the GGS halo round: a real SBM graph is
    partitioned host-side to get a true :class:`repro.graph.halo.
    HaloProgram`, whose per-step ``all_gather`` of cut-node features is the
    measured collective; ``meta`` carries the program's own byte accounting
    for comparison against the HLO scan.
    """
    from jax.sharding import PartitionSpec
    from repro.core.engine import EngineConfig, RoundProgram
    from repro.models.gnn import build_model
    from repro.optim import adam

    devs = jax.devices()
    if len(devs) < num_machines:
        raise ValueError(f"need ≥{num_machines} devices (have {len(devs)})")
    mesh = Mesh(np.asarray(devs[:num_machines]), ("machine",))
    model = build_model("GG", feature_dim, num_classes, hidden_dim=hidden_dim)
    engine_mode = "halo" if mode == "halo" else "local"
    program = RoundProgram(
        model, adam(1e-2), None,
        EngineConfig(num_machines=num_machines, mode=engine_mode,
                     backend="shard_map", with_correction=False,
                     halo_compression=halo_compression),
        mesh=mesh)
    params = model.init(0)
    state = program.init_state(params)
    Pn, K = num_machines, local_k
    pm = PartitionSpec("machine")
    meta: Dict[str, Any] = {"engine_mode": engine_mode}

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def abstract(tree, spec):
        return jax.tree_util.tree_map(
            lambda x: sds(x.shape, x.dtype, spec), tree)

    if mode == "halo":
        from repro.graph import sbm_graph
        from repro.graph.halo import build_halo_program, ext_fanout
        from repro.graph.partition import partition_graph
        data = sbm_graph(num_nodes=num_nodes, num_classes=num_classes,
                         feature_dim=feature_dim, feature_snr=0.3,
                         homophily=0.9, seed=0)
        part = partition_graph(data.graph, num_machines, method="bfs",
                               seed=0)
        halo = build_halo_program(data.graph, part)
        n_max = halo.n_ext_pad
        fanout = ext_fanout(halo.plan, fanout)
        meta.update(
            halo_max_send=halo.max_send, halo_max_halo=halo.max_halo,
            halo_compression=halo_compression,
            halo_bytes_per_step=halo.halo_bytes(
                feature_dim, compression=halo_compression),
            exchange_bytes_per_step=halo.exchange_bytes(
                feature_dim, compression=halo_compression),
            # compressed mode all-gathers int8 values AND f32 scales; the
            # wire-format pricing covers both collectives
            expected_all_gather_bytes=halo.gathered_bytes_per_device(
                feature_dim, compression=halo_compression))
    else:
        n_max = num_nodes // num_machines

    args = (abstract(params, P()), abstract(state.local_opt_state, P()),
            sds((Pn, n_max, feature_dim), jnp.float32, pm),
            sds((Pn, n_max), jnp.int32, pm),
            sds((Pn, K, n_max, fanout), jnp.int32, pm),
            sds((Pn, K, n_max, fanout), jnp.float32, pm),
            sds((Pn, K, batch_size), jnp.int32, pm),
            sds((Pn, K, batch_size), jnp.float32, pm),
            sds((K,), jnp.float32, PartitionSpec()))  # step_valid (replicated)
    if mode == "halo":
        args += (sds((Pn, halo.max_send), jnp.int32, pm),
                 sds((Pn, halo.max_halo), jnp.int32, pm),
                 sds((Pn, halo.max_halo), jnp.int32, pm),
                 sds((Pn, halo.max_halo), jnp.float32, pm))
    return program._round, args, mesh, meta


def run_gnn_engine_case(num_machines: int = 16, mode: str = "local",
                        **kw) -> DryrunResult:
    """Lower + compile the GNN engine round; record roofline inputs.

    For ``mode="halo"`` the result's meta also reports the
    :class:`~repro.graph.halo.HaloProgram` byte accounting next to the
    HLO-measured all-gather bytes (``halo_bytes_match`` — equal up to
    padding and the scan being lowered once, see acceptance check).
    """
    res = DryrunResult(arch="gnn-engine",
                       shape="round" if mode == "local" else "round-halo",
                       mesh=f"machine{num_machines}",
                       variant="llcg" if mode == "local" else "ggs-halo",
                       ok=False)
    try:
        fn, args, mesh, meta = build_gnn_engine_case(num_machines, mode=mode,
                                                     **kw)
        res.meta.update(meta)
        with mesh:
            t0 = time.perf_counter()
            lowered = fn.lower(*args)
            res.lower_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            compiled = lowered.compile()
            res.compile_s = time.perf_counter() - t0
            cost = cost_analysis_dict(compiled)
            res.flops = float(cost.get("flops", 0.0))
            res.bytes_accessed = float(cost.get("bytes accessed", 0.0))
            res.collective = collective_bytes_from_hlo(
                compiled.as_text(), mesh_shape=tuple(mesh.devices.shape))
            if mode == "halo":
                # the HLO scan counts the in-loop all-gather once; one
                # exchange's per-device result bytes is the comparable unit
                got = res.collective.get("all-gather", 0.0)
                want = meta["expected_all_gather_bytes"]
                res.meta["measured_all_gather_bytes"] = got
                res.meta["halo_bytes_match"] = bool(
                    got > 0 and want <= got <= 1.25 * want)
            res.ok = True
    except Exception as e:  # noqa: BLE001
        res.error = f"{type(e).__name__}: {e}"[:2000]
    return res


# ---------------------------------------------------------------- execution
def run_case(arch: str, shape_name: str, multi_pod: bool,
             variant: str = "llcg", llcg_k: int = 2, llcg_s: int = 1,
             remat: bool = True, cfg_override=None,
             keep_hlo: bool = False, unroll: bool = False,
             expert_hint: bool = False, avg_bf16: bool = False,
             serve_params_dtype: str = "float32") -> DryrunResult:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    res = DryrunResult(arch=arch, shape=shape_name, mesh=mesh_name,
                       variant=variant, ok=False)
    res.meta["llcg_k"] = llcg_k
    res.meta["llcg_s"] = llcg_s
    res.meta["remat"] = remat
    res.meta["unroll"] = unroll
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with mesh:
            fn, args = build_case(arch, shape_name, mesh, variant=variant,
                                  llcg_k=llcg_k, llcg_s=llcg_s, remat=remat,
                                  cfg_override=cfg_override, unroll=unroll,
                                  expert_hint=expert_hint, avg_bf16=avg_bf16,
                                  serve_params_dtype=serve_params_dtype)
            t0 = time.perf_counter()
            lowered = fn.lower(*args)
            res.lower_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            compiled = lowered.compile()
            res.compile_s = time.perf_counter() - t0

            try:
                mem = compiled.memory_analysis()
                if mem is not None:
                    for attr in ("argument_size_in_bytes",
                                 "output_size_in_bytes",
                                 "temp_size_in_bytes",
                                 "generated_code_size_in_bytes"):
                        v = getattr(mem, attr, None)
                        if v is not None:
                            res.memory[attr] = float(v)
            except Exception as e:  # noqa: BLE001
                res.memory["error"] = str(e)

            try:
                cost = cost_analysis_dict(compiled)
                res.flops = float(cost.get("flops", 0.0))
                res.bytes_accessed = float(cost.get("bytes accessed", 0.0))
            except Exception as e:  # noqa: BLE001
                res.meta["cost_error"] = str(e)

            try:
                hlo = compiled.as_text()
                res.collective = collective_bytes_from_hlo(
                    hlo, mesh_shape=tuple(mesh.devices.shape))
                if keep_hlo:
                    res.meta["hlo_len"] = len(hlo)
            except Exception as e:  # noqa: BLE001
                res.meta["hlo_error"] = str(e)

            res.ok = True
    except Exception as e:  # noqa: BLE001
        res.error = f"{type(e).__name__}: {e}"[:2000]
    return res


def roofline_terms(res: DryrunResult, chips: int) -> Dict[str, float]:
    """The three §Roofline terms, in seconds (per step, whole mesh)."""
    compute = res.flops / (chips * PEAK_FLOPS) if res.flops else 0.0
    memory = res.bytes_accessed / (chips * HBM_BW) if res.bytes_accessed else 0.0
    coll = res.collective.get("total", 0.0) / LINK_BW  # per-device bytes
    return {"compute_s": compute, "memory_s": memory, "collective_s": coll}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--variant", choices=["llcg", "sync"], default="llcg")
    ap.add_argument("--llcg-k", type=int, default=2)
    ap.add_argument("--llcg-s", type=int, default=1)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer scans for exact HLO cost accounting")
    ap.add_argument("--gnn-round", action="store_true",
                    help="also lower the unified GNN engine round program "
                         "(shard_map backend) on a virtual machine mesh")
    ap.add_argument("--gnn-machines", type=int, default=16)
    ap.add_argument("--gnn-mode", choices=["local", "halo", "both"],
                    default="both",
                    help="which GNN round modes to lower: the LLCG local "
                         "phase, the GGS halo-exchange round, or both")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    if args.gnn_round:
        os.makedirs(args.out, exist_ok=True)
        modes = (["local", "halo"] if args.gnn_mode == "both"
                 else [args.gnn_mode])
        # halo mode additionally verifies the compressed wire format
        # against the HLO (int8 values + f32 scales all-gathers)
        runs = [(m, "none") for m in modes]
        if "halo" in modes:
            runs.append(("halo", "int8"))
        all_ok = True
        for mode, halo_comp in runs:
            res = run_gnn_engine_case(args.gnn_machines, mode=mode,
                                      halo_compression=halo_comp)
            blob = dataclasses.asdict(res)
            stem = "gnn_engine" if mode == "local" else "gnn_engine_halo"
            if halo_comp != "none":
                stem += f"_{halo_comp}"
            fname = os.path.join(args.out, f"{stem}__machine"
                                           f"{args.gnn_machines}.json")
            with open(fname, "w") as f:
                json.dump(blob, f, indent=2)
            log.info("%s gnn-engine %s × %s: lower %.1fs compile %.1fs "
                     "coll=%.3e all-gather=%.3e %s",
                     "OK " if res.ok else "FAIL", res.shape, res.mesh,
                     res.lower_s, res.compile_s,
                     res.collective.get("total", 0),
                     res.collective.get("all-gather", 0), res.error or "")
            if mode == "halo" and res.ok:
                log.info("    halo accounting: exchange=%.3e B/step "
                         "(ideal %.3e), HLO all-gather match=%s",
                         res.meta.get("exchange_bytes_per_step", 0),
                         res.meta.get("halo_bytes_per_step", 0),
                         res.meta.get("halo_bytes_match"))
            all_ok &= res.ok
        if args.arch is None and not args.all:
            return 0 if all_ok else 1

    cases = []
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            if not shape_supported(a, s):
                log.info("skip %s × %s (per DESIGN.md skip rules)", a, s)
                continue
            for mp in meshes:
                cases.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    n_ok = 0
    for a, s, mp in cases:
        res = run_case(a, s, mp, variant=args.variant, llcg_k=args.llcg_k,
                       llcg_s=args.llcg_s, remat=not args.no_remat,
                       unroll=args.unroll)
        chips = 512 if mp else 256
        blob = dataclasses.asdict(res)
        blob["roofline"] = roofline_terms(res, chips)
        fname = os.path.join(args.out, f"{a}__{s}__{res.mesh}__{res.variant}.json")
        with open(fname, "w") as f:
            json.dump(blob, f, indent=2)
        status = "OK " if res.ok else "FAIL"
        log.info("%s %s × %s × %s: lower %.1fs compile %.1fs flops=%.3e "
                 "coll=%.3e %s", status, a, s, res.mesh, res.lower_s,
                 res.compile_s, res.flops, res.collective.get("total", 0),
                 res.error or "")
        n_ok += res.ok
    log.info("dry-run complete: %d/%d OK", n_ok, len(cases))
    return 0 if n_ok == len(cases) else 1


if __name__ == "__main__":
    sys.exit(main())
