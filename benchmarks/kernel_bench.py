"""Micro-benchmarks: Pallas kernels (interpret mode) vs pure-jnp oracles,
plus the aggregation-layout comparison (padded vs csr vs bcsr_kernel).

Wall-times on this CPU container measure the *emulated* kernel for the
Pallas rows, so their derived column reports correctness deltas rather than
speedups — the speedup claim lives in the roofline analysis.  The
aggregation-layout section is different: padded and csr are both pure-XLA
lowerings, so their wall-clock ratio is a real measurement.  It is written
to ``BENCH_kernels.json`` (min-over-interleaved-reps, the repo's bench
discipline) and CI gates on the committed baseline; the run itself asserts
the two layout-engine claims — csr ≥ 1.5× padded fwd+bwd at the
full-neighbor regime, and ``auto`` within 5% of the best hand-picked layout
at every bench shape.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph import rmat_graph, sbm_graph
from repro.graph.csr import build_neighbor_table
from repro.kernels import ref
from repro.kernels.ops import (
    spmm_aggregate, edge_softmax_aggregate, linear_scan, pallas_interpret,
)

# layouts backed by a Pallas kernel: emulated (and meaninglessly slow) when
# the container runs interpret mode — their timings are tagged and excluded
# from wall-clock comparisons
_PALLAS_LAYOUTS = ("bcsr_kernel",)
from repro.models.gnn.agg import build_agg_operands, choose_layout
from repro.models.gnn.layers import mean_aggregate
from repro.models.gnn.model import build_model

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_kernels.json")


def _time(fn, *args, reps=3) -> float:
    fn(*args)[0] if isinstance(fn(*args), tuple) else fn(*args)  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def bench_spmm() -> List[Dict]:
    ds = sbm_graph(num_nodes=512, feature_dim=64, seed=0)
    h = jnp.asarray(ds.features)
    us_k = _time(lambda x: spmm_aggregate(ds.graph, x), h)
    us_r = _time(lambda x: spmm_aggregate(ds.graph, x, use_ref=True), h)
    err = float(jnp.abs(spmm_aggregate(ds.graph, h)
                        - spmm_aggregate(ds.graph, h, use_ref=True)).max())
    return [{"name": "kernel_spmm_bcsr", "us_per_call": us_k,
             "derived": f"ref_us={us_r:.0f};max_err={err:.2e}"}]


def bench_edge_softmax() -> List[Dict]:
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.standard_normal((512, 16, 2)), jnp.float32)
    m = jnp.asarray((rng.random((512, 16)) > 0.3).astype(np.float32))
    z = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)
    t = jnp.asarray(rng.integers(0, 512, (512, 16)), jnp.int32)
    us_k = _time(edge_softmax_aggregate, s, m, z, t)
    err = float(jnp.abs(edge_softmax_aggregate(s, m, z, t)
                        - ref.edge_softmax_ref(s, m, z, t)).max())
    return [{"name": "kernel_edge_softmax", "us_per_call": us_k,
             "derived": f"max_err={err:.2e}"}]


def bench_linear_scan() -> List[Dict]:
    rng = np.random.default_rng(1)
    bh, t, dk, dv = 8, 512, 64, 64
    q = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, t, dv)), jnp.float32)
    lw = jnp.asarray(-0.1 * rng.random((bh, t, dk)), jnp.float32)
    us_k = _time(lambda *a: linear_scan(*a, chunk=64)[0], q, k, v, lw)
    us_seq = _time(lambda *a: ref.linear_scan_batched_ref(*a)[0], q, k, v, lw)
    yk, _ = linear_scan(q, k, v, lw, chunk=64)
    yr, _ = ref.linear_scan_batched_ref(q, k, v, lw)
    err = float(jnp.abs(yk - yr).max())
    return [{"name": "kernel_linear_scan", "us_per_call": us_k,
             "derived": f"seq_ref_us={us_seq:.0f};max_err={err:.2e}"}]


def _time_min(fns: Dict[str, callable], reps: int = 5) -> Dict[str, float]:
    """Seconds per call, min over ``reps`` INTERLEAVED repetitions — the
    repo's bench discipline: interleaving cancels drift, min cancels
    scheduler noise."""
    for f in fns.values():                      # warm / compile
        jax.block_until_ready(f())
    best = {k: float("inf") for k in fns}
    for _ in range(reps):
        for k, f in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            best[k] = min(best[k], time.perf_counter() - t0)
    return best


def bench_agg_layouts(reps: int = 5) -> Dict:
    """Aggregation-layout comparison on a degree-skewed power-law graph.

    Two regimes: the ``full_neighbor`` shape (table width = max degree —
    the server-correction / exact-serving regime where skew makes the
    padded table mostly zeros) and the ``sampled`` minibatch shape (narrow
    table — the local-round regime, where padded is the right layout and
    ``auto`` must keep picking it).  Each layout is timed on the aggregate
    op's forward+backward AND on the correction step itself
    (``value_and_grad`` of the model loss — exactly what ``corr_scan``
    executes per server step).
    """
    data = rmat_graph(num_nodes=1024, num_edges=6000, feature_dim=64,
                      num_classes=8, seed=0)
    g = data.graph
    feats = jnp.asarray(data.features)
    full_table, full_mask = build_neighbor_table(g)
    full_width = full_table.shape[1]
    sampled_width = 8
    rng = np.random.default_rng(0)
    samp_table = jnp.asarray(rng.integers(
        0, g.num_nodes, (g.num_nodes, sampled_width), dtype=np.int64))
    samp_mask = jnp.ones((g.num_nodes, sampled_width), jnp.float32)
    full_table, full_mask = jnp.asarray(full_table), jnp.asarray(full_mask)

    aggs = {lay: build_agg_operands(g, lay)
            for lay in ("padded", "csr", "bcsr_kernel")}

    @jax.jit
    def agg_fb(x, table, mask, agg):
        def loss(y):
            return (mean_aggregate(y, table, mask, agg=agg) ** 2).sum()
        return jax.value_and_grad(loss)(x)

    def section(table, mask, width, layouts):
        auto_lay = choose_layout("auto", num_nodes=g.num_nodes,
                                 num_edges=g.num_edges, width=width,
                                 full_width=full_width)
        fns = {lay: (lambda a=aggs[lay]: agg_fb(feats, table, mask, a))
               for lay in layouts}
        times = _time_min(fns, reps=reps)
        # interpret-mode Pallas timings measure the emulator, not the
        # kernel (seconds, not µs) — tag them and keep them out of the
        # auto-vs-best wall-clock comparison
        interpreted = [lay for lay in times
                       if lay in _PALLAS_LAYOUTS and pallas_interpret()]
        comparable = {k: v for k, v in times.items()
                      if k not in interpreted}
        out = {f"{k}_us": times[k] * 1e6 for k in times}
        out.update({f"{k}_interpreted": True for k in interpreted})
        # auto dispatches to its resolved layout's compiled function, so
        # its cost IS that layout's measurement
        out.update(width=width, auto_resolved=auto_lay,
                   interpreted_layouts=interpreted,
                   speedup_csr_vs_padded=(times["padded"] / times["csr"]
                                          if "csr" in times else None),
                   auto_vs_best=times[auto_lay] / min(comparable.values()))
        return out

    full = section(full_table, full_mask, full_width,
                   ("padded", "csr", "bcsr_kernel"))
    # sampled tables are different math from the full edge set — csr is not
    # an eligible layout there; the section checks auto keeps padded
    samp = section(samp_table, samp_mask, sampled_width, ("padded",))

    # correction-phase end-to-end: the jitted per-step value_and_grad the
    # engine's corr_scan runs, on the full-neighbor shape
    model = build_model("GGL", data.feature_dim, data.num_classes,
                        hidden_dim=64)
    params = model.init(0)
    labels = jnp.asarray(data.labels)
    batch = jnp.asarray(rng.integers(0, g.num_nodes, 64, dtype=np.int64))
    bmask = jnp.ones((64,), jnp.float32)

    from repro.core.machine import make_loss_fn
    corr_fb = jax.jit(jax.value_and_grad(make_loss_fn(model)))

    def corr_step(agg):
        return corr_fb(params, feats, full_table, full_mask, batch, labels,
                       bmask, agg)

    corr_times = _time_min(
        {"padded": lambda: corr_step(None),
         "csr": lambda: corr_step(aggs["csr"])}, reps=reps)
    corr = {f"{k}_us": corr_times[k] * 1e6 for k in corr_times}
    corr["speedup_csr_vs_padded"] = corr_times["padded"] / corr_times["csr"]

    result = {
        "config": {"num_nodes": g.num_nodes, "num_edges": g.num_edges,
                   "feature_dim": data.feature_dim,
                   "full_width": full_width,
                   "sampled_width": sampled_width, "reps": reps},
        "full_neighbor": full,
        "sampled": samp,
        "correction_step": corr,
    }

    assert full["speedup_csr_vs_padded"] >= 1.5, (
        f"csr layout must be ≥ 1.5x padded fwd+bwd at the full-neighbor "
        f"regime, measured {full['speedup_csr_vs_padded']:.2f}x "
        f"(min-over-{reps} interleaved reps)")
    for name, sec in (("full_neighbor", full), ("sampled", samp)):
        assert sec["auto_vs_best"] <= 1.05, (
            f"auto lost {sec['auto_vs_best']:.3f}x to the best hand-picked "
            f"layout at the {name} shape (budget 1.05x)")
    assert samp["auto_resolved"] == "padded"
    assert full["auto_resolved"] == "csr"
    return result


def agg_layout_rows() -> List[Dict]:
    """CSV rows for benchmarks.run; writes ``BENCH_kernels.json``."""
    result = bench_agg_layouts()
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=2)
    full, corr = result["full_neighbor"], result["correction_step"]
    return [
        {"name": "agg_full_neighbor_padded",
         "us_per_call": full["padded_us"],
         "derived": f"width={full['width']}"},
        {"name": "agg_full_neighbor_csr", "us_per_call": full["csr_us"],
         "derived": (f"speedup={full['speedup_csr_vs_padded']:.2f}x;"
                     f"auto={full['auto_resolved']}")},
        {"name": "agg_correction_step_csr", "us_per_call": corr["csr_us"],
         "derived": (f"padded_us={corr['padded_us']:.0f};"
                     f"speedup={corr['speedup_csr_vs_padded']:.2f}x")},
        {"name": "agg_sampled_padded",
         "us_per_call": result["sampled"]["padded_us"],
         "derived": f"auto={result['sampled']['auto_resolved']}"},
    ]


def all_rows() -> List[Dict]:
    return (bench_spmm() + bench_edge_softmax() + bench_linear_scan()
            + agg_layout_rows())


if __name__ == "__main__":
    for row in all_rows():
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
