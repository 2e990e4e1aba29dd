#!/usr/bin/env python3
"""Bring-up smoke run of LLCG training on a TPU, through the normal entry point.

    python chip_smoke.py            # one chip: device, train, kernels
    python chip_smoke.py --chips 4  # four chips: shard_map vs vmap only
    python chip_smoke.py --phase gat_stack  # the GAT stack, kernel vs XLA

Trains LLCG with ``build_trainer(data, model, plan).run()`` on a synthetic
SBM graph at ogbn-arxiv's published size (Hu et al. 2020, "Open Graph
Benchmark", ogbn-arxiv: 169,343 nodes, 1,166,243 edges, 128-d features, 40
classes) with the repo's ``ogb-arxiv`` base arch ``GBGBG`` at hidden width
256 (OGB's GCN baseline width for arxiv), then runs every Pallas kernel
compiled against its oracle in :mod:`repro.kernels.ref`, and the
benchmark's GAT stack through the attention kernel against its XLA path.  Weights and data
come from fixed seeds.

Every phase prints what it found.  A failed check exits non-zero before the
last line; the last line of a passing run is one JSON object naming the
device.  On a backend other than TPU it exits 1 and prints no result.  Runs
in one process (a chip belongs to one process at a time) and keeps the
compile cache where :func:`repro.core.plan.enable_compilation_cache` says.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.plan import (  # noqa: E402
    DistConfig, RoundSampler, build_trainer,
    enable_compilation_cache, llcg_plan, lower_plan,
)
from repro.graph import sbm_graph  # noqa: E402
from repro.graph.csr import subgraph_csr  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.ops import (  # noqa: E402
    dequantize_int8_rows, edge_softmax_aggregate, linear_scan,
    pallas_interpret, quantize_int8_rows, spmm_aggregate,
)
from repro.models.gnn import build_model  # noqa: E402

# ogbn-arxiv at its published size.  sbm_graph draws a Poisson(avg_degree)
# out-degree per node and stores both directions, so 6.9 gives ~1.17 M
# undirected (~2.33 M directed CSR) edges; homophily and feature SNR are
# the repo's ogb-arxiv setting (repro.configs.gnn_datasets).
ARXIV = dict(num_nodes=169_343, num_classes=40, feature_dim=128,
             avg_degree=6.9, homophily=0.9, feature_snr=0.3)
ARCH, HIDDEN = "GBGBG", 256
# the benchmark's arxiv-gat stack (the OGB ogbn-arxiv GAT of Wang et al.
# 2021): 3 layers of 3 heads × 250, residual projection, BatchNorm, the
# node's own slot in its softmax
GAT_STACK = dict(hidden_dim=250, num_heads=3, num_layers=3, residual=True,
                 self_loop=True, batch_norm=True)
# rwkv6-1.6b: 32 heads of 64
SCAN_HEADS, SCAN_HEAD_DIM = 32, 64
# Bound on |kernel - oracle| for a matmul on the MXU relative to the same
# sum over |terms|.  At default precision an f32 operand enters the MXU as
# bf16; truncated, it is off by up to 2^-8 relative, so a product is off by
# up to ~2^-7 (the v5e SpMM reaches 0.69 of that, more than round-to-nearest
# would allow).  2^-6 leaves a factor of two.  The oracles run at "highest".
MXU_REL = 2.0 ** -6


class CheckFailed(Exception):
    """A smoke check failed; the run exits non-zero without a result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, as
    reported through :mod:`jax.monitoring`."""

    def __init__(self):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------
def device_phase(chips: int):
    devices = jax.devices()
    d0 = devices[0]
    print(f"[device] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}")
    check(d0.platform == "tpu", f"no TPU: JAX found {d0.platform!r}")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, JAX found {len(devices)}")
    check(not pallas_interpret(), "Pallas kernels would run interpreted")
    print("[device] pallas_interpret=False")
    return devices


def make_graph(**overrides):
    t0 = time.perf_counter()
    data = sbm_graph(**{**ARXIV, **overrides}, seed=0, name="ogbn-arxiv-sbm")
    print(f"[graph] nodes={data.num_nodes} directed_edges="
          f"{data.graph.num_edges} feature_dim={data.feature_dim} "
          f"classes={data.num_classes} build_s={time.perf_counter() - t0:.1f}")
    return data


def make_plan(machines: int, rounds: int, compression: str = "none"):
    """LLCG with P machines, K=4 local steps, S=1 correction, fanout 10 and
    the round draw on the device."""
    plan = llcg_plan(DistConfig(num_machines=machines, rounds=rounds,
                                local_k=4, correction_steps=1, fanout=10,
                                seed=0))
    return dataclasses.replace(
        plan,
        sampler=dataclasses.replace(plan.sampler, placement="device"),
        comm=dataclasses.replace(plan.comm, compression=compression))


def train(data, model, plan, meter: CompileMeter, tag: str, **trainer_kw):
    """One ``build_trainer(...).run()``; prints per-round metrics and checks
    the losses are finite and the final score beats chance."""
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    hist = build_trainer(data, model, plan, **trainer_kw).run()
    wall = time.perf_counter() - t0
    c1, h1, m1 = meter.snapshot()
    local, corr = hist.meta["local_loss"], hist.meta["corr_loss"]
    corr_by_round = dict(zip(hist.meta["corr_rounds"], corr))
    for i, r in enumerate(hist.rounds):
        print(f"[{tag}] round={r} local_loss={local[i]:.6f} "
              f"corr_loss={corr_by_round.get(r, float('nan')):.6f} "
              f"val_score={hist.val_score[i]:.4f} "
              f"wall_s={hist.meta['round_seconds'][i]:.3f}")
    # compiles happen in round 1 (and round 2 under shard_map, when the
    # params first arrive replicated); the round walls show where
    print(f"[{tag}] compile_s={c1 - c0:.1f} cache_hits={h1 - h0} "
          f"cache_misses={m1 - m0} retraces={hist.meta['num_retraces']} "
          f"full_agg_slots={hist.meta['full_agg_slots']} "
          f"full_agg_edges={hist.meta['full_agg_edges']} "
          f"full_agg_buckets={hist.meta['full_agg_buckets']} "
          + "".join(f"{k}={hist.meta[k]} " for k in (
              "gat_local_slots", "gat_server_slots", "gat_chunk_bytes",
              "gat_kernel") if k in hist.meta)
          + f"run_s={wall:.1f}")
    losses = np.asarray(local + corr, np.float64)
    check(bool(np.all(np.isfinite(losses))), f"{tag}: non-finite loss")
    chance = 1.0 / data.num_classes
    check(hist.final_score > chance,
          f"{tag}: final val score {hist.final_score:.4f} ≤ chance {chance}")
    return hist


def _compare(name: str, got, want, bound, note: str = "") -> None:
    """Check ``|got - want| ≤ bound`` elementwise (``bound`` may be an
    array of per-element bounds)."""
    err = jnp.abs(jnp.asarray(got, jnp.float32) - jnp.asarray(want,
                                                               jnp.float32))
    worst = float(jnp.max(err / jnp.maximum(bound, 1e-30)))
    print(f"[kernels] {name} max_err={float(jnp.max(err)):.3e} "
          f"worst_err_over_bound={worst:.3e}{note}")
    check(worst <= 1.0, f"{name}: error exceeds its bound")


def kernel_phase(data, machines: int = 8, spmm_nodes: int = 8192,
                 edge_rows: int = 4096, fanout: int = 10,
                 scan_batch: int = 2, scan_len: int = 256) -> None:
    """Every Pallas kernel against its oracle at this run's widths."""
    key = jax.random.PRNGKey(0)
    ks = iter(jax.random.split(key, 16))
    hi = lambda: jax.default_matmul_precision("highest")  # noqa: E731

    # int8 quantize / dequantize: the int8_ef averaging payload of the
    # GBGBG weight leaves, flattened per machine as compress_tree does
    for shape in ((data.feature_dim, HIDDEN), (HIDDEN, data.num_classes)):
        cols = shape[0] * shape[1]
        x = 1e-3 * jax.random.normal(next(ks), (machines, cols))
        u = jax.random.uniform(next(ks), (machines, cols))
        q, s = quantize_int8_rows(x, u)
        q_r, s_r = ref.quantize_int8_rows_ref(x, u)
        # x/scale may differ by an ulp between Mosaic and XLA, moving
        # floor(x/scale + u) across an integer: at most one step
        off = int(jnp.sum(q != q_r))
        _compare(f"quantize(P={machines},C={cols})", q, q_r, 1.0,
                 f" steps_off={off}/{q.size}")
        # one f32 divide each side: 2^-22 relative covers both roundings
        _compare(f"quantize_scale(P={machines},C={cols})", s, s_r,
                 2.0 ** -22 * s_r)
        out = dequantize_int8_rows(q_r, s_r)
        out_r = ref.dequantize_int8_rows_ref(q_r, s_r)
        # one f32 multiply each side
        _compare(f"dequantize(P={machines},C={cols})", out, out_r,
                 2.0 ** -22 * jnp.abs(out_r))

    # BCSR SpMM: mean aggregation over an induced slice of the graph
    sub, _ = subgraph_csr(data.graph, np.arange(spmm_nodes))
    h = jax.random.normal(next(ks), (spmm_nodes, HIDDEN))
    out = spmm_aggregate(sub, h)
    with hi():
        out_r = spmm_aggregate(sub, h, use_ref=True)
        mag = spmm_aggregate(sub, jnp.abs(h), use_ref=True)
    _compare(f"spmm_aggregate(n={spmm_nodes},d={HIDDEN},"
             f"edges={sub.num_edges})", out, out_r, MXU_REL * mag,
             " bound=2^-6·|A||h|")

    # GAT edge attention, rows gathered in the kernel, 2 heads: f32 on the
    # VPU, so f32 rounding only — a few ulps per exp/divide/add over F
    # terms; 1e-5 of Σ α|z| leaves margin and is far below what a bf16 pass
    # would cost (4e-3)
    scores = jax.random.normal(next(ks), (edge_rows, fanout, 2))
    mask = (jax.random.uniform(next(ks), (edge_rows, fanout)) > 0.2
            ).astype(jnp.float32).at[:8].set(0.0)   # a few isolated rows
    z = jax.random.normal(next(ks), (edge_rows, HIDDEN))
    table = jax.random.randint(next(ks), (edge_rows, fanout), 0, edge_rows)
    out = edge_softmax_aggregate(scores, mask, z, table)
    with hi():
        out_r = ref.edge_softmax_ref(scores, mask, z, table)
        mag = ref.edge_softmax_ref(scores, mask, jnp.abs(z), table)
    _compare(f"edge_softmax(n={edge_rows},F={fanout},d={HIDDEN},heads=2)",
             out, out_r, 1e-5 * mag, " bound=1e-5·Σα|z|")

    # gated linear scan at rwkv6-1.6b's head size.  log_w ∈ [-0.15, 0]:
    # the chunked form needs |Σ log_w| over a chunk well inside f32's exp
    # range
    bh, d = scan_batch * SCAN_HEADS, SCAN_HEAD_DIM
    q, k, v = (jax.random.normal(next(ks), (bh, scan_len, d))
               for _ in range(3))
    lw = -0.15 * jax.random.uniform(next(ks), (bh, scan_len, d))
    bonus = jax.random.normal(next(ks), (bh, d))
    y, h_t = linear_scan(q, k, v, lw)
    y_s, h_s = linear_scan(q, k, v, lw, strict=True, u=bonus)
    with hi():
        y_r, h_r = ref.linear_scan_batched_ref(q, k, v, lw)
        y_m, h_m = ref.linear_scan_batched_ref(jnp.abs(q), jnp.abs(k),
                                               jnp.abs(v), lw)
        y_sr, h_sr = linear_scan(q, k, v, lw, strict=True, u=bonus,
                                 use_ref=True)
        y_sm, _ = linear_scan(jnp.abs(q), jnp.abs(k), jnp.abs(v), lw,
                              strict=True, u=jnp.abs(bonus), use_ref=True)
    shape = f"(BH={bh},T={scan_len},dk=dv={d})"
    note = " bound=2^-6·|terms|"
    _compare(f"linear_scan{shape}.y", y, y_r, MXU_REL * y_m, note)
    _compare(f"linear_scan{shape}.h_T", h_t, h_r, MXU_REL * h_m, note)
    _compare(f"linear_scan_strict{shape}.y", y_s, y_sr, MXU_REL * y_sm,
             note)
    _compare(f"linear_scan_strict{shape}.h_T", h_s, h_sr, MXU_REL * h_m,
             note)


def gat_stack_phase(data, batch: int = 64) -> None:
    """The whole GAT stack at arxiv width over the full graph's degree
    buckets, jitted through the attention kernel against the XLA path: the
    forward alone (the evaluation's program) and the forward with the
    gradient of a batch's loss (the correction's), bucket by bucket."""
    from repro.models.gnn.agg import bucketed_operands
    agg = bucketed_operands(data.graph)
    n = data.graph.num_nodes
    feats = jnp.asarray(data.features)
    nodes = jnp.asarray(np.random.default_rng(0).choice(n, batch, False))
    labels = jnp.asarray(data.labels)[nodes]
    none_i = jnp.zeros((n, 0), jnp.int32)
    none_f = jnp.zeros((n, 0), jnp.float32)
    out = {}
    for fused in (True, False):
        model = build_model("GAT", data.feature_dim, data.num_classes,
                            fused_gat=fused, **GAT_STACK)
        params = model.init(0)

        def forward(p, m=model):
            return m.apply(p, feats, none_i, none_f, agg=agg)

        def loss(p):
            logits = forward(p)
            logp = jax.nn.log_softmax(logits[nodes])
            return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                                 axis=1)), logits
        with jax.default_matmul_precision("high"):
            fwd = jax.jit(forward)(params)
            (value, logits), grads = jax.jit(
                jax.value_and_grad(loss, has_aux=True))(params)
        out[fused] = (np.asarray(fwd), np.asarray(logits), float(value),
                      grads)
    # the two paths differ only in the order of the attention's f32 sums:
    # ~1e-5 of a logit through three layers; 1e-3 of (1 + |logit|) leaves
    # margin, and a wrong row is off by O(1)
    order = np.asarray(agg.buckets.order)
    for prog, k in (("forward", 0), ("gradient", 1)):
        got, want = out[True][k], out[False][k]
        err = np.abs(got - want).max(axis=1)
        bound = 1e-3 * (1.0 + np.abs(want).max(axis=1))
        start = 0
        for table in agg.buckets.tables:
            rows = order[start:start + table.shape[0]]
            start += table.shape[0]
            bad = int(np.sum(err[rows] > bound[rows]))
            print(f"[gat_stack] {prog} bucket w={table.shape[1]} "
                  f"rows={rows.size} max_err={err[rows].max(initial=0.0):.3e}"
                  f" bad_rows={bad}")
            check(bad == 0, f"gat_stack {prog}: {bad} rows of the "
                            f"{table.shape[1]}-slot bucket differ between "
                            f"kernel and XLA")
    # a correct loss agrees to ~1e-6; the kernel's wrong correction rows
    # once moved it by 1e-2
    loss_err = abs(out[True][2] - out[False][2]) / abs(out[False][2])
    grad_err = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                   for a, b in zip(jax.tree_util.tree_leaves(out[True][3]),
                                   jax.tree_util.tree_leaves(out[False][3])))
    print(f"[gat_stack] batch={batch} loss_rel_err={loss_err:.3e} "
          f"worst_leaf_grad_rel_err={grad_err:.3e}")
    check(loss_err <= 1e-4 and grad_err <= 1e-3,
          "gat_stack: the batch loss or its gradient differs between "
          "kernel and XLA")


def _spans(name: str, arr, devices) -> None:
    """``arr``'s leading (machine) axis is split one slice per device."""
    placed = {s.device for s in arr.addressable_shards}
    rows = {s.data.shape[0] for s in arr.addressable_shards}
    print(f"[4chip] {name} shape={tuple(arr.shape)} devices={len(placed)} "
          f"rows_per_device={sorted(rows)}")
    check(placed == set(devices) and rows == {arr.shape[0] // len(devices)},
          f"{name} is not split over the machine axis")


def multi_chip_phase(data, model, devices, meter: CompileMeter,
                     rounds: int = 3) -> None:
    """The same LLCG plan at P=4: ``shard_map`` with one machine per chip
    against ``vmap`` on one chip."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(devices), ("machine",))
    plan = make_plan(machines=len(devices), rounds=rounds)

    sampler = RoundSampler(data, model, plan, mesh=mesh)
    inputs = sampler.sample(lower_plan(plan)[0])
    for name, arr in (("feats", sampler.feats_j),
                      ("labels", sampler.labels_j),
                      ("tables", inputs.tables), ("masks", inputs.masks),
                      ("batches", inputs.batches)):
        _spans(name, arr, devices)
    del sampler, inputs

    sharded = train(data, model, plan, meter, "shard_map", backend="shard_map",
                    mesh=mesh)
    single = train(data, model, plan, meter, "vmap")
    # The noise floor: the same vmap run on features perturbed by 2^-9
    # relative (bf16 rounding).  Adam turns rounding-level differences in
    # near-zero gradients into lr-sized steps, so params of two correct
    # runs drift apart by about this much; a wrong collective or a dropped
    # machine moves the losses far beyond it.
    rng = np.random.default_rng(1)
    noisy = dataclasses.replace(data, features=(data.features * (
        1.0 + 2.0 ** -9 * rng.standard_normal(data.features.shape))
    ).astype(np.float32))
    floor = train(noisy, model, plan, meter, "vmap_perturbed")
    loss_tol = 1e-2
    la = np.asarray(sharded.meta["local_loss"])
    lb = np.asarray(single.meta["local_loss"])
    loss_err = float(np.max(np.abs(la - lb) / np.abs(lb)))
    params_err = _rel_dist(sharded.meta["final_params"],
                           single.meta["final_params"])
    params_floor = _rel_dist(floor.meta["final_params"],
                             single.meta["final_params"])
    print(f"[4chip] local_loss_rel_err={loss_err:.3e} tol={loss_tol:.0e} "
          f"params_rel_err={params_err:.3e} "
          f"perturbed_params_rel_err={params_floor:.3e} "
          f"tol={2 * params_floor:.3e}")
    check(loss_err <= loss_tol, "shard_map and vmap local losses disagree")
    check(params_err <= 2 * params_floor,
          "shard_map and vmap final params disagree beyond the noise floor")


def _rel_dist(a, b) -> float:
    """‖a − b‖ / ‖b‖ over all leaves of two param trees."""
    pa, pb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    diff = sum(float(jnp.sum((jnp.asarray(x) - jnp.asarray(y)) ** 2))
               for x, y in zip(pa, pb))
    norm = sum(float(jnp.sum(jnp.asarray(y) ** 2)) for y in pb)
    return float(np.sqrt(diff / norm))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard_map-vs-vmap phase on a "
                         "four-chip mesh")
    ap.add_argument("--phase", choices=("all", "gat_stack"), default="all",
                    help="gat_stack: on one chip, run only the GAT stack's "
                         "kernel-against-XLA comparison")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    cache_dir = enable_compilation_cache()
    devices = device_phase(args.chips)
    print(f"[device] compilation_cache={cache_dir}")
    meter = CompileMeter()
    data = make_graph()
    model = build_model(ARCH, data.feature_dim, data.num_classes,
                        hidden_dim=HIDDEN)
    if args.chips == 4:
        multi_chip_phase(data, model, devices[:4], meter)
    elif args.phase == "gat_stack":
        gat_stack_phase(data)
    else:
        train(data, model, make_plan(machines=8, rounds=5), meter, "train")
        train(data, model, make_plan(machines=8, rounds=2,
                                     compression="int8_ef"),
              meter, "train_int8_ef")
        kernel_phase(data)
        gat_stack_phase(data)
    print(f"[done] total_s={time.perf_counter() - t_start:.1f}")
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except CheckFailed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        sys.exit(1)
