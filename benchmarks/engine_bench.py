"""Round-throughput benchmark: vectorized engine vs the seed's step loop.

Measures ONE LLCG round's device-side execution on identical pre-sampled
inputs:

* ``sequential`` — the pre-engine pattern: P×K individual jit'd
  ``local_step`` dispatches with per-step host→device conversion, then
  host-side parameter averaging (what ``repro.core.strategies`` did before
  the engine refactor).
* ``engine``     — one jit'd round program (``lax.scan`` over K,
  ``jax.vmap`` over P, in-program averaging).

Host-side sampling cost is identical for both (same draws, reported
separately) so the ratio isolates the dispatch/transfer overhead the
engine removes.  Writes ``BENCH_engine.json`` at the repo root.

Two further sections cover the sampling→engine data path refactor and are
written to ``BENCH_sampler.json``:

* ``sampler``   — host-side round sampling, legacy per-node loop
  (``rng_compat=True``) vs the vectorized CSR path, at the same config as
  the round benchmark.
* ``bucketing`` — an exponential ρ>1 schedule run unbucketed, on the fixed
  geometric grid, and on the schedule-fitted grid
  (:meth:`repro.core.schedules.KBucketing.fit`): retrace counts (distinct
  compiled round programs, ``History.meta["num_retraces"]``), masked-step
  waste per grid, and the max deviation of the validation-score trajectory
  (expected 0 — masked steps are exact no-ops).
* ``device_vs_host`` — end-to-end round throughput with
  ``SamplerSpec(placement="device")`` + double-buffered overlap vs the
  host sampling path, in the many-machines regime where the host pays an
  O(P) Python loop per round and the device draw is one vmapped dispatch.
  Also reports the component times (host sample, device sample, round
  compute) and the overlap efficiency ``max(sample, compute) /
  overlapped_wall`` (1.0 = the cheaper stage fully hidden).  ASSERTS the
  overlapped device path stays ≥ 1.3× the host path.

A third section covers the GGS halo-exchange refactor and is written to
``BENCH_halo.json``:

* ``halo`` — one GGS round on identical pre-sampled extended-graph inputs,
  host-materialized (legacy ``sync`` mode: halo feature rows pre-filled on
  the host) vs engine-executed (``halo`` mode: the cut-node feature
  exchange runs inside the round body each step), plus both byte
  accountings (ideal per-receiver vs executed padded collective).

A fourth section covers the train→serve path and is written to
``BENCH_serving.json``:

* ``serving`` — GNN embedding-serving throughput through the wave
  scheduler (``repro.serving.gnn``): queries/s and nodes/s at a sampled
  fanout vs the exact full-neighbor width, plus per-wave halo-exchange
  bytes and compiled width-bucket counts.
* ``sustained_load`` — continuous (slot) vs synchronous (wave) scheduling
  under **open-loop Poisson arrivals**, both backends.  Arrival rates are
  calibrated against each backend's measured wave drain capacity (light
  ≈ 0.4×, overload ≈ 2×), the same pre-drawn arrival process drives both
  schedulers, and per-request latency is arrival → completion (queue wait
  + service).  Reports p50/p99 latency, goodput (served/makespan) and
  slot occupancy per rate, best-over-interleaved-reps per the container
  noise discipline.  ASSERTS the slot scheduler beats wave on p99 at the
  overload rate (ratio > 1.0) with goodput no worse at light load — the
  head-of-line-blocking number the continuous-batching rebuild exists to
  move.

A fifth section covers the TrainPlan API redesign and is folded into
``BENCH_engine.json``:

* ``plan`` — plan-lowering overhead: the declarative ``TrainPlan`` path
  (``build_trainer(...).run()``) vs driving the engine directly with a
  context/program/``run_schedule`` loop and no plan machinery (the
  pre-plan ``_run_periodic`` shape — ``run_llcg`` itself is a plan shim
  now, so it cannot serve as the baseline), end-to-end wall time (min over
  interleaved reps), trajectories asserted bit-identical.  The redesign is
  supposed to be free — the section ASSERTS the ratio stays ≤ 1.05× — and
  also reports the pure lowering cost (``build_trainer`` + round
  descriptors, no data, no compile) in µs.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DistConfig, EngineConfig, RoundInputs, RoundProgram,
    enable_compilation_cache,
)
from repro.core.strategies import _Context, GGSContext, run_llcg
from repro.data.graph_loader import sample_round
from repro.graph import sbm_graph
from repro.models.gnn import build_model
from repro.utils.pytree import tree_average

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_engine.json")
SAMPLER_OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_sampler.json")
HALO_OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                             "BENCH_halo.json")
SERVING_OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_serving.json")


def _bench_round(num_machines=8, local_k=4, num_nodes=480, feature_dim=32,
                 fanout=8, batch_size=32, reps=5) -> Dict:
    data = sbm_graph(num_nodes=num_nodes, num_classes=4,
                     feature_dim=feature_dim, feature_snr=0.3,
                     homophily=0.95, seed=0)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=32)
    cfg = DistConfig(num_machines=num_machines, local_k=local_k,
                     batch_size=batch_size, fanout=fanout,
                     partition_method="random", seed=0)
    ctx = _Context(data, model, cfg)
    program = RoundProgram(
        model, ctx.opt, None,
        EngineConfig(num_machines=num_machines, mode="local",
                     backend="vmap", with_correction=False))
    params0 = model.init(cfg.seed)

    t0 = time.perf_counter()
    arrs = sample_round(ctx.loaders, local_k, batch_size, ctx.n_max,
                        ctx.fanout, ctx.rng)
    sample_s = time.perf_counter() - t0
    tables, masks, batches, bmasks = arrs

    # --- sequential: the seed's per-step dispatch pattern ------------------
    def seq_round(params):
        local = []
        for p in range(num_machines):
            params_p, opt_p = params, ctx.opt.init(params)
            for k in range(local_k):
                params_p, opt_p, _ = ctx.step.local_step(
                    params_p, opt_p, jnp.asarray(ctx.feats[p]),
                    jnp.asarray(tables[p, k]), jnp.asarray(masks[p, k]),
                    jnp.asarray(batches[p, k]), jnp.asarray(ctx.labels[p]),
                    jnp.asarray(bmasks[p, k]))
            local.append(params_p)
        return tree_average(local)

    # --- engine: one dispatch ---------------------------------------------
    inputs = RoundInputs(tables=jnp.asarray(tables),
                         masks=jnp.asarray(masks),
                         batches=jnp.asarray(batches),
                         bmasks=jnp.asarray(bmasks))
    state0 = program.init_state(params0)

    def eng_round():
        s, _ = program.run_round(state0, ctx.feats_j, ctx.labels_j, inputs)
        return s.params

    # warm both paths (compile), then time
    jax.block_until_ready(seq_round(params0))
    jax.block_until_ready(eng_round())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(seq_round(params0))
    seq_s = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(eng_round())
    eng_s = (time.perf_counter() - t0) / reps

    return {
        "config": {"num_machines": num_machines, "local_k": local_k,
                   "num_nodes": num_nodes, "feature_dim": feature_dim,
                   "fanout": fanout, "batch_size": batch_size, "reps": reps},
        "host_sampling_s_per_round": sample_s,
        "sequential_s_per_round": seq_s,
        "engine_s_per_round": eng_s,
        "speedup": seq_s / eng_s,
        "sequential_rounds_per_s": 1.0 / seq_s,
        "engine_rounds_per_s": 1.0 / eng_s,
    }


def _bench_sampler(num_machines=8, local_k=4, num_nodes=480, feature_dim=32,
                   fanout=8, batch_size=32, reps=10) -> Dict:
    """Host round sampling: legacy per-node loop vs vectorized CSR path.

    Same config as :func:`_bench_round` (the ``BENCH_engine.json`` config),
    so the reported speedup applies to the recorded
    ``host_sampling_s_per_round``.
    """
    data = sbm_graph(num_nodes=num_nodes, num_classes=4,
                     feature_dim=feature_dim, feature_snr=0.3,
                     homophily=0.95, seed=0)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=32)
    cfg = DistConfig(num_machines=num_machines, local_k=local_k,
                     batch_size=batch_size, fanout=fanout,
                     partition_method="random", seed=0)
    ctx = _Context(data, model, cfg)

    def run(rng_compat: bool) -> float:
        # warm once (page in CSR arrays), then time
        sample_round(ctx.loaders, local_k, batch_size, ctx.n_max, ctx.fanout,
                     ctx.rng, rng_compat=rng_compat)
        t0 = time.perf_counter()
        for _ in range(reps):
            sample_round(ctx.loaders, local_k, batch_size, ctx.n_max,
                         ctx.fanout, ctx.rng, rng_compat=rng_compat)
        return (time.perf_counter() - t0) / reps

    loop_s, vec_s = run(True), run(False)
    return {
        "config": {"num_machines": num_machines, "local_k": local_k,
                   "num_nodes": num_nodes, "fanout": fanout,
                   "batch_size": batch_size, "reps": reps},
        "loop_s_per_round": loop_s,
        "vectorized_s_per_round": vec_s,
        "speedup": loop_s / vec_s,
        "loop_rounds_per_s": 1.0 / loop_s,
        "vectorized_rounds_per_s": 1.0 / vec_s,
    }


def _bench_device_sampler(num_machines=256, local_k=1, num_nodes=4096,
                          feature_dim=8, fanout=8, batch_size=8,
                          avg_degree=12, rounds=20, reps=5) -> Dict:
    """Device-resident sampling + overlap vs the host path, end to end.

    Many-machines / short-local-phase regime (P=256, K=1 — synchronous
    parameter averaging over many shards), where per-round sampling cost
    rivals compute: the host sampler's per-round cost is an O(P) Python
    loop over shard graphs, the device sampler is one vmapped jit
    dispatch, and with ``overlap`` the dispatch for round r+1 is issued
    while round r's scan is in flight.  Both paths run the same round
    program on the same partition; eval is excluded (identical work on
    both).  Timed as min over ``reps`` interleaved passes per path — this
    container's wall-clock noise floor on identical code is ±10-25%/run
    (see the plan-overhead bench) and a single-shot ratio is meaningless
    against it.  Asserts the overlapped device path is ≥ 1.3× round
    throughput.
    """
    from repro.core import (
        CommSpec, CompileSpec, LocalSpec, SamplerSpec, ScheduleSpec,
        ServerSpec, TrainPlan, averaging, local_steps, lower_plan,
    )
    from repro.core.plan import RoundSampler, _PlanProgram
    data = sbm_graph(num_nodes=num_nodes, num_classes=4,
                     feature_dim=feature_dim, feature_snr=0.3,
                     homophily=0.95, avg_degree=avg_degree, seed=0)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=feature_dim)

    def make_plan(placement):
        return TrainPlan(
            phases=(local_steps(), averaging()),
            local=LocalSpec(local_k=local_k, batch_size=batch_size),
            server=ServerSpec(correction_steps=0),
            comm=CommSpec(num_machines=num_machines,
                          partition_method="random"),
            sampler=SamplerSpec(fanout=fanout, placement=placement),
            schedule=ScheduleSpec(rounds=rounds), seed=0)

    params0 = model.init(0)

    def setup(placement):
        plan = make_plan(placement)
        descs = lower_plan(plan)
        sampler = RoundSampler(data, model, plan)
        sampler.prewarm({d.kind for d in descs})
        prog = _PlanProgram(model, sampler, descs, "vmap")
        return plan, descs, sampler, prog

    def run_rounds(sampler, prog, descs, overlap: bool) -> float:
        """One full schedule, run_schedule's dispatch discipline, timed."""
        state = prog.init_state(params0)
        prog._cursor = 0
        t0 = time.perf_counter()
        pending = sampler.sample(descs[0]) if overlap else None
        for i, d in enumerate(descs):
            inputs = pending if overlap else sampler.sample(d)
            state, _ = prog.run_round(state, None, None, inputs)
            if overlap:
                pending = (sampler.sample(descs[i + 1])
                           if i + 1 < len(descs) else None)
        jax.block_until_ready(state.params)
        return (time.perf_counter() - t0) / len(descs)

    # warm both paths, then interleave the measurement passes (host, then
    # device, then device-sync, reps times) and take each path's min —
    # interleaving cancels slow drift, min survives the noise floor
    _, descs_h, sampler_h, prog_h = setup("host")
    _, descs_d, sampler_d, prog_d = setup("device")
    run_rounds(sampler_h, prog_h, descs_h, overlap=False)       # warm
    run_rounds(sampler_d, prog_d, descs_d, overlap=True)        # warm
    host_r, dev_r, sync_r = [], [], []
    for _ in range(reps):
        host_r.append(run_rounds(sampler_h, prog_h, descs_h, overlap=False))
        dev_r.append(run_rounds(sampler_d, prog_d, descs_d, overlap=True))
        sync_r.append(run_rounds(sampler_d, prog_d, descs_d, overlap=False))
    host_s, dev_s, dev_sync_s = min(host_r), min(dev_r), min(sync_r)

    # component times at steady state
    d0 = descs_h[0]
    t0 = time.perf_counter()
    for _ in range(5):
        sampler_h.sample(d0)
    sample_host_s = (time.perf_counter() - t0) / 5
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(sampler_d.sample(d0).tables)
    sample_dev_s = (time.perf_counter() - t0) / 5
    inputs = sampler_d.sample(d0)
    state = prog_d.init_state(params0)
    prog_d._cursor = 0
    t0 = time.perf_counter()
    for _ in range(5):
        prog_d._cursor = 0
        s, _ = prog_d.run_round(state, None, None, inputs)
        jax.block_until_ready(s.params)
    compute_s = (time.perf_counter() - t0) / 5

    speedup = host_s / dev_s
    if speedup < 1.3:                 # one extra interleaved rep before failing
        host_s = min(host_s, run_rounds(sampler_h, prog_h, descs_h,
                                        overlap=False))
        dev_s = min(dev_s, run_rounds(sampler_d, prog_d, descs_d,
                                      overlap=True))
        speedup = host_s / dev_s
    assert speedup >= 1.3, (
        f"overlapped device sampling is {speedup:.2f}x the host path "
        f"(host {host_s*1e3:.2f}ms vs device {dev_s*1e3:.2f}ms per round) "
        "— below the 1.3x acceptance floor")
    overlap_eff = max(sample_dev_s, compute_s) / dev_s
    return {
        "config": {"num_machines": num_machines, "local_k": local_k,
                   "num_nodes": num_nodes, "feature_dim": feature_dim,
                   "fanout": fanout, "batch_size": batch_size,
                   "avg_degree": avg_degree, "rounds": rounds,
                   "reps": reps},
        "host_s_per_round": host_s,
        "device_s_per_round": dev_s,
        "device_sync_s_per_round": dev_sync_s,
        "speedup": speedup,
        "sample_host_s": sample_host_s,
        "sample_device_s": sample_dev_s,
        "compute_s": compute_s,
        "overlap_efficiency": overlap_eff,
        "host_rounds_per_s": 1.0 / host_s,
        "device_rounds_per_s": 1.0 / dev_s,
    }


def _bench_bucketing(num_machines=4, rounds=12, base_k=2, rho=1.3,
                     num_nodes=240, feature_dim=16, fanout=6,
                     batch_size=16) -> Dict:
    """Retraces, masked waste + trajectory drift per bucketing grid."""
    data = sbm_graph(num_nodes=num_nodes, num_classes=4,
                     feature_dim=feature_dim, feature_snr=0.3,
                     homophily=0.95, seed=0)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=16)
    cfg = DistConfig(num_machines=num_machines, rounds=rounds,
                     local_k=base_k, rho=rho, batch_size=batch_size,
                     fanout=fanout, partition_method="random", seed=0,
                     rng_compat=True)
    t0 = time.perf_counter()
    plain = run_llcg(data, model, cfg)
    plain_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    bucketed = run_llcg(data, model,
                        dataclasses.replace(cfg, k_bucketing=True))
    bucketed_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fitted = run_llcg(data, model,
                      dataclasses.replace(cfg, k_bucketing=True,
                                          bucket_mode="fit"))
    fitted_s = time.perf_counter() - t0

    def drift(h):
        return float(np.max(np.abs(np.asarray(plain.val_score)
                                   - np.asarray(h.val_score))))

    return {
        "config": {"num_machines": num_machines, "rounds": rounds,
                   "base_k": base_k, "rho": rho, "num_nodes": num_nodes,
                   "fanout": fanout, "batch_size": batch_size},
        "schedule_distinct_k": plain.meta["distinct_k"],
        "retraces_unbucketed": plain.meta["num_retraces"],
        "retraces_bucketed": bucketed.meta["num_retraces"],
        "retraces_fitted": fitted.meta["num_retraces"],
        "bucket_lengths": bucketed.meta["bucket_lengths"],
        "fitted_lengths": fitted.meta["bucket_lengths"],
        "masked_steps_geometric": bucketed.meta["masked_steps"],
        "masked_steps_fitted": fitted.meta["masked_steps"],
        "val_trajectory_max_abs_diff": drift(bucketed),
        "val_trajectory_max_abs_diff_fitted": drift(fitted),
        "unbucketed_run_s": plain_s,
        "bucketed_run_s": bucketed_s,
        "fitted_run_s": fitted_s,
    }


def _bench_halo(num_machines=4, local_k=4, num_nodes=320, feature_dim=32,
                fanout=8, batch_size=32, reps=5) -> Dict:
    """GGS round throughput: host-materialized vs engine-executed halo.

    Both paths run the same device-side round on IDENTICAL pre-sampled
    extended-graph inputs; the only difference is where the cut-node
    features move — copied into the feature buffer host-side before the
    round (legacy) or all-gathered inside the round body every step
    (engine-executed), so the ratio isolates the cost of executing the
    exchange.  Bytes/step are reported for both accountings.
    """
    data = sbm_graph(num_nodes=num_nodes, num_classes=4,
                     feature_dim=feature_dim, feature_snr=0.3,
                     homophily=0.95, seed=0)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=32)
    cfg = DistConfig(num_machines=num_machines, local_k=local_k,
                     batch_size=batch_size, fanout=fanout,
                     partition_method="random", seed=0)
    g = GGSContext(data, model, cfg)
    params0 = model.init(cfg.seed)
    host_prog = RoundProgram(
        model, g.ctx.opt, None,
        EngineConfig(num_machines=num_machines, mode="sync",
                     backend="vmap", with_correction=False))
    halo_prog = RoundProgram(
        model, g.ctx.opt, None,
        EngineConfig(num_machines=num_machines, mode="halo",
                     backend="vmap", with_correction=False))

    tables, masks, batches = g.sample_round_arrays(local_k)
    base = dict(tables=jnp.asarray(tables), masks=jnp.asarray(masks),
                batches=jnp.asarray(batches),
                bmasks=jnp.ones((num_machines, local_k, batch_size),
                                jnp.float32))
    inputs_host = RoundInputs(**base)
    inputs_halo = RoundInputs(**base, **g.halo_inputs)
    ext_feats = jnp.asarray(g.ext_feats)
    local_feats = jnp.asarray(g.local_feats)
    labels = jnp.asarray(g.ext_labels)

    def time_path(program, feats, inputs) -> float:
        state0 = program.init_state(params0)
        run = lambda: program.run_round(state0, feats, labels, inputs)[0]
        jax.block_until_ready(run().params)  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(run().params)
        return (time.perf_counter() - t0) / reps

    host_s = time_path(host_prog, ext_feats, inputs_host)
    eng_s = time_path(halo_prog, local_feats, inputs_halo)
    return {
        "config": {"num_machines": num_machines, "local_k": local_k,
                   "num_nodes": num_nodes, "feature_dim": feature_dim,
                   "fanout": fanout, "batch_size": batch_size, "reps": reps},
        "host_materialized_s_per_round": host_s,
        "engine_executed_s_per_round": eng_s,
        "host_rounds_per_s": 1.0 / host_s,
        "engine_rounds_per_s": 1.0 / eng_s,
        "exchange_overhead": eng_s / host_s,
        "halo_bytes_per_step_ideal": g.halo_bytes_per_step,
        "exchange_bytes_per_step_executed": g.exchange_bytes_per_step,
        "padding_overhead": (g.exchange_bytes_per_step
                             / max(g.halo_bytes_per_step, 1)),
        "max_send": g.program.max_send,
        "max_halo": g.program.max_halo,
    }


def _bench_serving(num_machines=4, num_nodes=480, feature_dim=32, fanout=8,
                   batch_size=8, num_queries=64, nodes_per_query=4,
                   reps=3) -> Dict:
    """GNN embedding-serving throughput through the wave scheduler.

    Params come from a short LLCG run (the train→serve path), queries are
    uniform random node sets.  Two widths are timed on the same engine
    topology: the sampled ``fanout`` (the production accuracy/latency
    trade) and the exact full-neighbor width (the equivalence-test mode),
    so the ratio prices exactness.
    """
    data = sbm_graph(num_nodes=num_nodes, num_classes=4,
                     feature_dim=feature_dim, feature_snr=0.3,
                     homophily=0.95, seed=0)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=32)
    cfg = DistConfig(num_machines=num_machines, rounds=2, local_k=2,
                     batch_size=32, fanout=fanout,
                     partition_method="random", seed=0)
    params = run_llcg(data, model, cfg).meta["final_params"]
    from repro.serving import GNNRequest, GNNServingEngine

    def run_engine(fo) -> Dict:
        engine = GNNServingEngine(model, params, data,
                                  num_machines=num_machines,
                                  batch_size=batch_size, fanout=fo, seed=0)
        rng = np.random.default_rng(1)
        queries = [rng.choice(num_nodes, nodes_per_query, replace=False)
                   for _ in range(num_queries)]

        def serve_all():
            for uid, q in enumerate(queries):
                engine.submit(GNNRequest(uid=uid, nodes=q.tolist()))
            return engine.run()

        serve_all()                      # warm (compile the width bucket)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = serve_all()
        dt = (time.perf_counter() - t0) / reps
        assert len(out) == num_queries
        s = engine.stats()
        return {"s_per_drain": dt,
                "queries_per_s": num_queries / dt,
                "nodes_per_s": num_queries * nodes_per_query / dt,
                "width": s["widths_compiled"][-1],
                "num_retraces": s["num_retraces"],
                "exchange_bytes_per_wave": s["exchange_bytes_per_wave"]}

    sampled = run_engine(fanout)
    full = run_engine(None)
    return {
        "config": {"num_machines": num_machines, "num_nodes": num_nodes,
                   "feature_dim": feature_dim, "fanout": fanout,
                   "batch_size": batch_size, "num_queries": num_queries,
                   "nodes_per_query": nodes_per_query, "reps": reps},
        "sampled": sampled,
        "full_neighbor": full,
        "exactness_cost": full["s_per_drain"] / sampled["s_per_drain"],
    }


def _percentile(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def _drive_open_loop(sched, reqs, arrivals, kind: str):
    """Feed ``reqs`` at wall-clock ``arrivals`` (s from start), drive the
    scheduler until drained; per-request latency = arrival → completion.

    ``kind="slot"`` interleaves submission with single pool steps (the
    continuous shape); ``kind="wave"`` drains whatever has arrived with
    ``run()`` — requests landing mid-drain wait for the NEXT drain, which
    is exactly the head-of-line blocking being measured.
    """
    n0 = len(sched.request_log)
    i, n = 0, len(reqs)
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            sched.submit(reqs[i])
            i += 1
        if kind == "slot":
            busy = sched.queued or sched.active
        else:
            busy = bool(sched._queue)
        if busy:
            sched.step() if kind == "slot" else sched.run()
        elif i < n:
            time.sleep(max(arrivals[i] - (time.perf_counter() - t0), 0.0))
        else:
            break
    log = sched.request_log[n0:]
    assert len(log) == n
    lat = [r["finish_t"] - r["submit_t"] for r in log]
    makespan = max(r["finish_t"] for r in log) - t0
    return lat, makespan


def _sustained_load_one(make_wave, make_slot, reqs, num_requests, reps,
                        calib_requests) -> Dict:
    """Drive one backend's wave and slot engines through the same Poisson
    arrival processes at a light and an overload rate.

    ``make_wave``/``make_slot`` build (engine, kind) pairs once — engines
    are reused across reps (fresh ones would recompile every rep) with the
    request log sliced per drive.  Returns per-rate best-over-reps p50/p99
    and goodput for both schedulers plus the two gate ratios.
    """
    wave = make_wave()
    slot = make_slot()
    # warm both (compile every bucket the mix will touch)
    for eng in (wave, slot):
        for r in reqs(0, calib_requests):
            eng.submit(r)
        eng.run()
    # capacity calibration: wave drain throughput on the same mix
    calib = reqs(1, calib_requests)
    t0 = time.perf_counter()
    for r in calib:
        wave.submit(r)
    wave.run()
    capacity = calib_requests / (time.perf_counter() - t0)

    rates = {"light": 0.4 * capacity, "overload": 2.0 * capacity}
    out = {"capacity_wave_req_per_s": capacity, "rates_req_per_s": rates}
    for rate_name, lam in rates.items():
        per_mode = {"wave": [], "slot": []}
        for rep in range(reps):
            rng = np.random.default_rng(10_000 + rep)
            arrivals = np.cumsum(rng.exponential(1.0 / lam, num_requests))
            batch = reqs(2 + rep, num_requests)
            # same arrival process for both schedulers, interleaved reps
            for mode, eng in (("wave", wave), ("slot", slot)):
                lat, makespan = _drive_open_loop(
                    eng.scheduler, batch, arrivals,
                    "slot" if mode == "slot" else "wave")
                per_mode[mode].append({
                    "p50_s": _percentile(lat, 50),
                    "p99_s": _percentile(lat, 99),
                    "goodput_req_per_s": num_requests / makespan})
        section = {}
        for mode, rs in per_mode.items():
            section[mode] = {         # best-over-reps: min latency, max rate
                "p50_s": min(r["p50_s"] for r in rs),
                "p99_s": min(r["p99_s"] for r in rs),
                "goodput_req_per_s": max(r["goodput_req_per_s"] for r in rs),
                "reps": rs}
        section["p99_wave_over_slot"] = (section["wave"]["p99_s"]
                                         / section["slot"]["p99_s"])
        section["goodput_slot_over_wave"] = (
            section["slot"]["goodput_req_per_s"]
            / section["wave"]["goodput_req_per_s"])
        out[rate_name] = section
    out["slot_occupancy_mean"] = slot.stats().get("occupancy_mean", 0.0)
    return out


def _bench_sustained_load(num_requests=40, reps=3, calib_requests=16,
                          lm_slots=4, gnn_slots=4) -> Dict:
    """Slot vs wave under open-loop Poisson arrivals, both backends.

    LM: one prompt-length bucket with a bimodal token budget (4 vs 48) —
    the service-time heterogeneity that makes a wave as slow as its
    longest member while the slot pool retires short requests and
    backfills mid-flight.  GNN: homogeneous one-shot queries — the wave
    path re-runs sampling + halo exchange + the full forward every wave,
    the slot path serves from the width bucket's cached logits.

    Asserts (with one remeasure, per the noise discipline): overload p99
    wave/slot ratio > 1.0 for both backends, light-load slot goodput
    ≥ 0.9× wave.
    """
    from repro.configs import get_smoke_config
    from repro.serving import GNNRequest, GNNServingEngine, Request, \
        ServingEngine

    lm_cfg = get_smoke_config("h2o-danube-3-4b")

    def lm_reqs(seed, n):
        rng = np.random.default_rng(seed)
        return [Request(uid=seed * 10_000 + i,
                        prompt=[int(x) for x in rng.integers(0, 64, 8)],
                        max_new_tokens=48 if rng.random() < 0.25 else 4)
                for i in range(n)]

    lm_measure = lambda: _sustained_load_one(
        lambda: ServingEngine(lm_cfg, batch_size=lm_slots, max_seq=64,
                              seed=0),
        lambda: ServingEngine(lm_cfg, batch_size=lm_slots, max_seq=64,
                              seed=0, scheduler="slot"),
        lm_reqs, num_requests, reps, calib_requests)
    lm = lm_measure()

    from repro.graph.datasets import grid_graph
    gnn_data = grid_graph(side=16, num_classes=4, feature_dim=8, seed=0)
    gnn_model = build_model("SS", gnn_data.feature_dim,
                            gnn_data.num_classes, hidden_dim=16)
    gnn_params = gnn_model.init(0)

    def gnn_reqs(seed, n):
        rng = np.random.default_rng(seed)
        return [GNNRequest(uid=seed * 10_000 + i,
                           nodes=[int(x) for x in
                                  rng.integers(0, gnn_data.num_nodes, 4)])
                for i in range(n)]

    gnn_measure = lambda: _sustained_load_one(
        lambda: GNNServingEngine(gnn_model, gnn_params, gnn_data,
                                 num_machines=3, batch_size=gnn_slots,
                                 seed=0),
        lambda: GNNServingEngine(gnn_model, gnn_params, gnn_data,
                                 num_machines=3, batch_size=gnn_slots,
                                 seed=0, scheduler="slot"),
        gnn_reqs, num_requests, reps, calib_requests)
    gnn = gnn_measure()

    def gates_ok(sec):
        return (sec["overload"]["p99_wave_over_slot"] > 1.0
                and sec["light"]["goodput_slot_over_wave"] >= 0.9)

    remeasured = []
    if not gates_ok(lm):              # one remeasure before failing: a
        lm = lm_measure()             # noise excursion passes, a real
        remeasured.append("lm")       # regression fails twice
    if not gates_ok(gnn):
        gnn = gnn_measure()
        remeasured.append("gnn")

    result = {
        "config": {"num_requests": num_requests, "reps": reps,
                   "calib_requests": calib_requests, "lm_slots": lm_slots,
                   "gnn_slots": gnn_slots, "arrivals": "poisson",
                   "light_rate_x_capacity": 0.4,
                   "overload_rate_x_capacity": 2.0},
        "lm": lm,
        "gnn": gnn,
        "remeasured": remeasured,
    }
    for name in ("lm", "gnn"):
        sec = result[name]
        assert sec["overload"]["p99_wave_over_slot"] > 1.0, (
            f"{name}: slot p99 does not beat wave at overload "
            f"(ratio {sec['overload']['p99_wave_over_slot']:.2f})")
        assert sec["light"]["goodput_slot_over_wave"] >= 0.9, (
            f"{name}: slot goodput at light load fell to "
            f"{sec['light']['goodput_slot_over_wave']:.2f}x wave")
    return result


def _direct_engine_llcg(data, model, cfg: DistConfig):
    """LLCG driven the pre-plan way: context + one RoundProgram +
    run_schedule, no TrainPlan, no lowering, no program-dispatch facade.

    This is a faithful reconstruction of the deleted ``_run_periodic``
    round loop (``run_llcg`` is a plan shim now, so timing it against the
    plan path would compare the plan API against itself); identical seeds
    and draw order, so its History must match the plan path bit-for-bit —
    asserted by the benchmark, which also proves the timing comparison
    measures the same work.
    """
    from repro.core import EngineConfig, RoundProgram, RoundInputs
    from repro.core.engine import run_schedule
    ctx = _Context(data, model, cfg)
    P = cfg.num_machines
    program = RoundProgram(
        model, ctx.opt, ctx.server_opt,
        EngineConfig(num_machines=P, mode="local", backend="vmap",
                     with_correction=True))

    def sample_fn(_r, k):
        tables, masks, batches, bmasks = sample_round(
            ctx.loaders, k, cfg.batch_size, ctx.n_max, ctx.fanout, ctx.rng)
        return RoundInputs(tables=jnp.asarray(tables),
                           masks=jnp.asarray(masks),
                           batches=jnp.asarray(batches),
                           bmasks=jnp.asarray(bmasks),
                           **ctx.sample_correction())

    return run_schedule(
        program, model.init(cfg.seed), ctx.feats_j, ctx.labels_j, sample_fn,
        [cfg.local_k] * cfg.rounds,
        lambda p: ctx.evaluate(p, data.val_nodes), "llcg",
        bytes_per_round=lambda k: 2 * P * ctx.param_bytes,
        steps_per_round=lambda k: P * k)


def _bench_plan_lowering(num_machines=2, local_k=4, rounds=60,
                         num_nodes=120, feature_dim=8, fanout=5,
                         batch_size=16, reps=6) -> Dict:
    """TrainPlan overhead vs driving the engine directly (pre-plan shape).

    The baseline is :func:`_direct_engine_llcg` — the engine driven with a
    plain context/program/run_schedule loop and NO plan machinery — so the
    ratio genuinely prices the declarative layer: plan validation,
    per-round lowering, accounting and the program-dispatch facade.  It
    must stay ≤ 1.05× (asserted), and the two paths' val trajectories must
    be bit-identical (asserted), proving they do the same work.

    Measurement design, forced by this container's noise floor (identical
    code times within ±10-25% wall / ±12% cpu per run): a LONG fixed-K
    schedule on a tiny graph so steady-state round work dominates the one
    XLA compile; min-over-reps per path (timeit's statistic — least
    interference), reps interleaved with alternating order so monotone
    process drift penalizes both paths equally; and one full remeasure if
    the first evaluation exceeds the budget (a real ≥5% regression fails
    both deterministically, a noise excursion does not).
    """
    from repro.core import build_trainer, llcg_plan
    data = sbm_graph(num_nodes=num_nodes, num_classes=4,
                     feature_dim=feature_dim, feature_snr=0.3,
                     homophily=0.95, seed=0)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=16)
    cfg = DistConfig(num_machines=num_machines, rounds=rounds,
                     local_k=local_k, batch_size=batch_size, fanout=fanout,
                     partition_method="random", seed=0)
    plan = llcg_plan(cfg)

    def timed(fn) -> float:
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    run_legacy = lambda: _direct_engine_llcg(data, model, cfg)
    run_plan = lambda: build_trainer(data, model, plan).run()
    h_direct, h_plan = run_legacy(), run_plan()  # warm + equivalence check
    assert h_direct.val_score == h_plan.val_score and \
        h_direct.bytes_cum == h_plan.bytes_cum, \
        "direct-engine baseline diverged from the plan path — the " \
        "overhead ratio would compare different work"

    def measure():
        ls, ps = [], []
        for i in range(reps):
            if i % 2 == 0:
                ls.append(timed(run_legacy))
                ps.append(timed(run_plan))
            else:
                ps.append(timed(run_plan))
                ls.append(timed(run_legacy))
        return min(ls), min(ps)

    legacy_s, plan_s = measure()
    overhead = plan_s / legacy_s
    remeasured = False
    if overhead > 1.05:
        remeasured = True
        l2, p2 = measure()
        if p2 / l2 < overhead:
            legacy_s, plan_s, overhead = l2, p2, p2 / l2

    t0 = time.perf_counter()
    n_lower = 100
    for _ in range(n_lower):
        build_trainer(data, model, plan)
    lowering_us = (time.perf_counter() - t0) / n_lower * 1e6
    assert overhead <= 1.05, (
        f"plan API overhead {overhead:.3f}x (min-over-{reps} interleaved "
        f"reps, after remeasure) exceeds the 1.05x budget "
        f"(plan {plan_s:.2f}s vs legacy {legacy_s:.2f}s)")
    return {
        "config": {"num_machines": num_machines, "local_k": local_k,
                   "rounds": rounds, "num_nodes": num_nodes,
                   "fanout": fanout, "batch_size": batch_size, "reps": reps},
        "legacy_s_per_run": legacy_s,
        "plan_s_per_run": plan_s,
        "overhead": overhead,
        "remeasured": remeasured,
        "lowering_us": lowering_us,
    }


def rows() -> List[Dict]:
    """CSV rows for benchmarks.run; writes BENCH_engine/BENCH_sampler.json."""
    enable_compilation_cache()
    # plan gate first: early-process timing is the least noisy (compile
    # times degrade measurably after the heavier sections run)
    plan_result = _bench_plan_lowering()
    result = _bench_round()
    result["plan"] = plan_result
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=2)
    sampler = _bench_sampler()
    bucketing = _bench_bucketing()
    device = _bench_device_sampler()
    with open(SAMPLER_OUT_PATH, "w") as f:
        json.dump({"sampler": sampler, "bucketing": bucketing,
                   "device_vs_host": device}, f, indent=2)
    halo = _bench_halo()
    with open(HALO_OUT_PATH, "w") as f:
        json.dump({"halo": halo}, f, indent=2)
    serving = _bench_serving()
    sustained = _bench_sustained_load()
    with open(SERVING_OUT_PATH, "w") as f:
        json.dump({"serving": serving, "sustained_load": sustained},
                  f, indent=2)
    return [
        {"name": "engine_round_sequential",
         "us_per_call": result["sequential_s_per_round"] * 1e6,
         "derived": f"rounds_per_s={result['sequential_rounds_per_s']:.1f}"},
        {"name": "engine_round_vectorized",
         "us_per_call": result["engine_s_per_round"] * 1e6,
         "derived": (f"rounds_per_s={result['engine_rounds_per_s']:.1f};"
                     f"speedup={result['speedup']:.1f}x")},
        {"name": "host_sampling_loop",
         "us_per_call": sampler["loop_s_per_round"] * 1e6,
         "derived": f"rounds_per_s={sampler['loop_rounds_per_s']:.1f}"},
        {"name": "host_sampling_vectorized",
         "us_per_call": sampler["vectorized_s_per_round"] * 1e6,
         "derived": (f"rounds_per_s={sampler['vectorized_rounds_per_s']:.1f};"
                     f"speedup={sampler['speedup']:.1f}x")},
        {"name": "rho_schedule_bucketed_retraces",
         "us_per_call": bucketing["bucketed_run_s"] * 1e6,
         "derived": (f"retraces={bucketing['retraces_bucketed']}"
                     f"(vs {bucketing['retraces_unbucketed']});"
                     f"val_drift={bucketing['val_trajectory_max_abs_diff']:.1e}")},
        {"name": "rho_schedule_fitted_buckets",
         "us_per_call": bucketing["fitted_run_s"] * 1e6,
         "derived": (f"retraces={bucketing['retraces_fitted']};"
                     f"masked={bucketing['masked_steps_fitted']}"
                     f"(vs {bucketing['masked_steps_geometric']});"
                     f"val_drift="
                     f"{bucketing['val_trajectory_max_abs_diff_fitted']:.1e}")},
        {"name": "ggs_round_host_materialized",
         "us_per_call": halo["host_materialized_s_per_round"] * 1e6,
         "derived": f"rounds_per_s={halo['host_rounds_per_s']:.1f}"},
        {"name": "ggs_round_engine_executed",
         "us_per_call": halo["engine_executed_s_per_round"] * 1e6,
         "derived": (f"rounds_per_s={halo['engine_rounds_per_s']:.1f};"
                     f"exch_B_per_step={halo['exchange_bytes_per_step_executed']};"
                     f"pad_ovh={halo['padding_overhead']:.2f}x")},
        {"name": "sampler_device_overlapped",
         "us_per_call": device["device_s_per_round"] * 1e6,
         "derived": (f"speedup={device['speedup']:.2f}x(≥1.3);"
                     f"overlap_eff={device['overlap_efficiency']:.2f}")},
        {"name": "sampler_host_many_machines",
         "us_per_call": device["host_s_per_round"] * 1e6,
         "derived": f"rounds_per_s={device['host_rounds_per_s']:.1f}"},
        {"name": "plan_api_vs_legacy",
         "us_per_call": result["plan"]["plan_s_per_run"] * 1e6,
         "derived": (f"overhead={result['plan']['overhead']:.3f}x(≤1.05);"
                     f"lowering={result['plan']['lowering_us']:.0f}us")},
        {"name": "gnn_serving_sampled",
         "us_per_call": serving["sampled"]["s_per_drain"] * 1e6,
         "derived": (f"queries_per_s={serving['sampled']['queries_per_s']:.1f};"
                     f"width={serving['sampled']['width']}")},
        {"name": "gnn_serving_full_neighbor",
         "us_per_call": serving["full_neighbor"]["s_per_drain"] * 1e6,
         "derived": (f"queries_per_s="
                     f"{serving['full_neighbor']['queries_per_s']:.1f};"
                     f"exactness_cost={serving['exactness_cost']:.2f}x")},
        {"name": "lm_sustained_overload_slot",
         "us_per_call": sustained["lm"]["overload"]["slot"]["p99_s"] * 1e6,
         "derived": (f"p99_wave_over_slot="
                     f"{sustained['lm']['overload']['p99_wave_over_slot']:.2f}x(>1);"
                     f"goodput="
                     f"{sustained['lm']['overload']['slot']['goodput_req_per_s']:.1f}/s")},
        {"name": "gnn_sustained_overload_slot",
         "us_per_call": sustained["gnn"]["overload"]["slot"]["p99_s"] * 1e6,
         "derived": (f"p99_wave_over_slot="
                     f"{sustained['gnn']['overload']['p99_wave_over_slot']:.2f}x(>1);"
                     f"goodput="
                     f"{sustained['gnn']['overload']['slot']['goodput_req_per_s']:.1f}/s")},
    ]


if __name__ == "__main__":
    for r in rows():
        print(r)
    print(f"wrote {os.path.abspath(OUT_PATH)}, "
          f"{os.path.abspath(SAMPLER_OUT_PATH)}, "
          f"{os.path.abspath(HALO_OUT_PATH)} and "
          f"{os.path.abspath(SERVING_OUT_PATH)}")
