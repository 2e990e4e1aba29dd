"""Aggregation-layout engine: every layout must be the padded path's exact
twin.

The contract under test (repro.models.gnn.agg): ``csr`` and ``bcsr_kernel``
replace the padded dense-gather aggregation with edge-centric / Pallas-BCSR
lowerings of the SAME math — so forward outputs AND parameter gradients must
match the padded oracle on full-neighbor tables, across degree-skewed
graphs, zero-degree nodes (all-pad GAT rows) and every normalization.  On
top of the op-level sweeps: the cost model's resolution rules, end-to-end
correction-trajectory equality through the plan API, retrace accounting
(layout selection must not add per-round recompiles), serving equivalence
on both scheduler shapes, and operand caching / dtype preservation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import (
    DistConfig, LocalSpec, ServerSpec, build_trainer, llcg_plan,
)
from repro.graph.csr import build_neighbor_table, symmetric_normalizers
from repro.graph.datasets import rmat_graph, sbm_graph
from repro.kernels.ops import edge_softmax_aggregate, spmm_aggregate
from repro.models.gnn import layers as L
from repro.models.gnn.agg import (
    AUTO_THRESHOLD, build_agg_operands, choose_layout, edge_operands,
    stacked_edge_operands,
)
from repro.models.gnn.model import build_model
from repro.serving.gnn import GNNRequest, GNNServingEngine


# degree-skewed power-law graph WITH zero-degree nodes (all-pad table rows)
@pytest.fixture(scope="module")
def skewed():
    data = rmat_graph(num_nodes=150, num_edges=600, feature_dim=12,
                      num_classes=5, seed=3)
    assert (data.graph.degrees() == 0).any(), "fixture must cover deg-0 rows"
    table, mask = build_neighbor_table(data.graph)
    return data, jnp.asarray(table), jnp.asarray(mask)


LAYOUTS_UNDER_TEST = ("csr", "bcsr_kernel")


# --------------------------------------------------------------------------
# Op-level equivalence: forward AND gradient vs the padded oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("layout", LAYOUTS_UNDER_TEST)
def test_mean_and_sym_aggregate_match_padded(skewed, layout):
    data, table, mask = skewed
    agg = build_agg_operands(data.graph, layout)
    h = jnp.asarray(data.features)
    nrm = jnp.asarray(symmetric_normalizers(data.graph))

    np.testing.assert_allclose(
        np.asarray(L.mean_aggregate(h, table, mask, agg=agg)),
        np.asarray(L.mean_aggregate(h, table, mask)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(L.sym_aggregate(h, table, mask, nrm, agg=agg)),
        np.asarray(L.sym_aggregate(h, table, mask, nrm)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layout", LAYOUTS_UNDER_TEST)
def test_aggregate_gradients_match_padded(skewed, layout):
    data, table, mask = skewed
    agg = build_agg_operands(data.graph, layout)
    h = jnp.asarray(data.features)

    def loss(x, a):
        return (L.mean_aggregate(x, table, mask, agg=a) ** 2).sum()

    g_pad = jax.grad(loss)(h, None)
    g_lay = jax.grad(loss)(h, agg)
    np.testing.assert_allclose(np.asarray(g_lay), np.asarray(g_pad),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("layout", LAYOUTS_UNDER_TEST)
@pytest.mark.parametrize("arch", ["GGL", "SSL", "GAT", "APPNP"])
def test_model_forward_and_param_grads_match_padded(skewed, layout, arch):
    data, table, mask = skewed
    agg = build_agg_operands(data.graph, layout)
    model = build_model(arch, data.feature_dim, data.num_classes,
                        hidden_dim=8, appnp_steps=4)
    params = model.init(0)
    feats = jnp.asarray(data.features)

    def loss(p, a):
        return (model.apply(p, feats, table, mask, agg=a) ** 2).mean()

    l_pad, g_pad = jax.value_and_grad(loss)(params, None)
    l_lay, g_lay = jax.value_and_grad(loss)(params, agg)
    np.testing.assert_allclose(float(l_lay), float(l_pad),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_lay),
                    jax.tree_util.tree_leaves(g_pad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


def test_gat_zero_degree_rows_are_zero(skewed):
    """All-pad rows (zero-degree nodes): the padded path emits zeros; the
    edge-centric softmax must agree instead of producing NaNs."""
    data, table, mask = skewed
    zero = np.flatnonzero(data.graph.degrees() == 0)
    model = build_model("GAT", data.feature_dim, data.num_classes,
                        hidden_dim=8)
    params = model.init(0)
    feats = jnp.asarray(data.features)
    agg = build_agg_operands(data.graph, "csr")
    out = np.asarray(model.apply(params, feats, table, mask, agg=agg))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[zero], 0.0, atol=1e-6)


def test_layouts_work_inside_scan(skewed):
    """corr_scan / APPNP shape: aggregation under lax.scan + jit + grad."""
    data, table, mask = skewed
    feats = jnp.asarray(data.features)
    model = build_model("GGL", data.feature_dim, data.num_classes,
                        hidden_dim=8)
    params = model.init(0)

    @jax.jit
    def scanned(p, a):
        def body(c, _):
            return c + (model.apply(p, feats, table, mask, agg=a)**2).mean(), 0.
        out, _ = jax.lax.scan(body, 0.0, None, length=2)
        return out

    ref = float(scanned(params, None))
    for layout in LAYOUTS_UNDER_TEST:
        agg = build_agg_operands(data.graph, layout)
        assert float(scanned(params, agg)) == pytest.approx(ref, rel=1e-5)
        g = jax.grad(lambda p: scanned(p, agg))(params)
        assert all(bool(jnp.isfinite(x).all())
                   for x in jax.tree_util.tree_leaves(g))


# --------------------------------------------------------------------------
# Cost model + knob validation
# --------------------------------------------------------------------------
def test_choose_layout_rules():
    # non-auto passes through untouched
    for lay in ("padded", "csr", "bcsr_kernel"):
        assert choose_layout(lay, num_nodes=10, num_edges=10, width=1,
                             full_width=64) == lay
    # sampled / narrowed tables are different math → always padded
    assert choose_layout("auto", num_nodes=1000, num_edges=10, width=32,
                         full_width=64) == "padded"
    assert choose_layout("auto", num_nodes=1000, num_edges=10, width=64,
                         full_width=64, sampled=True) == "padded"
    # full-width, mostly-padding table → csr
    assert choose_layout("auto", num_nodes=1000, num_edges=1000, width=64,
                         full_width=64) == "csr"
    # full-width but genuinely dense table → padded
    assert choose_layout("auto", num_nodes=100, num_edges=100 * 64,
                         width=64, full_width=64) == "padded"
    # threshold boundary: padded_work == threshold·E picks csr
    e = 1000
    w = int(AUTO_THRESHOLD * e) // 100
    assert choose_layout("auto", num_nodes=100, num_edges=e, width=w,
                         full_width=w) == "csr"
    with pytest.raises(ValueError, match="unknown aggregation layout"):
        choose_layout("dense", num_nodes=1, num_edges=1, width=1,
                      full_width=1)


def test_spec_layout_validation():
    with pytest.raises(ValueError, match="agg_layout"):
        LocalSpec(agg_layout="csr")          # local rounds are sampled math
    with pytest.raises(ValueError, match="unknown"):
        ServerSpec(agg_layout="dense")
    with pytest.raises(ValueError, match="correction_sampling"):
        ServerSpec(agg_layout="csr", correction_sampling=True)
    with pytest.raises(ValueError, match="unknown agg_layout"):
        build_model("GG", 4, 2, agg_layout="dense")
    assert ServerSpec(agg_layout="auto").agg_layout == "auto"


# --------------------------------------------------------------------------
# End-to-end: correction through the plan API
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def plan_hists():
    data = rmat_graph(num_nodes=160, num_edges=700, feature_dim=10,
                      num_classes=4, seed=5)
    model = build_model("GGL", data.feature_dim, data.num_classes,
                        hidden_dim=8)
    hists = {}
    for lay in ("padded", "csr", "auto"):
        cfg = DistConfig(num_machines=2, rounds=2, local_k=2, batch_size=16,
                         server_batch_size=16, correction_steps=2, fanout=5,
                         partition_method="random", server_agg_layout=lay,
                         seed=0)
        hists[lay] = build_trainer(data, model, llcg_plan(cfg)).run()
    return hists


def test_correction_trajectory_identical_across_layouts(plan_hists):
    ref = plan_hists["padded"]
    for lay in ("csr", "auto"):
        h = plan_hists[lay]
        np.testing.assert_allclose(h.train_loss, ref.train_loss,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(h.val_score, ref.val_score,
                                   rtol=1e-5, atol=1e-6)


def test_layout_selection_adds_no_retraces(plan_hists):
    """The layout knob must not cause per-round recompiles: every layout
    compiles the local path once and the correction path once."""
    ref = plan_hists["padded"]
    for lay in ("csr", "auto"):
        h = plan_hists[lay]
        assert h.meta["num_retraces"] == ref.meta["num_retraces"]
        assert h.meta["num_corr_retraces"] == 1
    assert ref.meta["num_corr_retraces"] == 1
    # auto resolves against the full-table geometry: power-law skew makes
    # the single table mostly padding, but its degree buckets gather fewer
    # slots than twice the edges, so padded (on the buckets) stays
    assert plan_hists["auto"].meta["corr_agg_layout"] == "padded"
    assert plan_hists["csr"].meta["corr_agg_layout"] == "csr"
    assert plan_hists["padded"].meta["corr_agg_layout"] == "padded"


# --------------------------------------------------------------------------
# Serving: full-width buckets through the edge-centric path
# --------------------------------------------------------------------------
@pytest.mark.parametrize("scheduler", ["wave", "slot"])
def test_serving_predictions_identical_across_layouts(scheduler):
    data = rmat_graph(num_nodes=140, num_edges=600, feature_dim=10,
                      num_classes=4, seed=7)
    model = build_model("GGL", data.feature_dim, data.num_classes,
                        hidden_dim=8)
    params = model.init(0)
    rng = np.random.default_rng(0)
    reqs = [(i, [int(v) for v in rng.integers(0, data.num_nodes, 6)])
            for i in range(4)]
    preds = {}
    for lay in ("padded", "csr", "auto"):
        eng = GNNServingEngine(model, params, data, num_machines=2,
                               scheduler=scheduler, agg_layout=lay)
        for uid, nodes in reqs:
            eng.submit(GNNRequest(uid=uid, nodes=nodes))
        preds[lay] = {r.uid: r.predictions for r in eng.run()}
        assert eng.stats()["agg_layout"] == lay
    assert preds["padded"] == preds["csr"] == preds["auto"]


def test_serving_rejects_bcsr_and_narrow_stays_padded():
    data = sbm_graph(num_nodes=80, feature_dim=8, num_classes=3, seed=1)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=8)
    params = model.init(0)
    with pytest.raises(ValueError, match="bcsr_kernel"):
        GNNServingEngine(model, params, data, num_machines=2,
                         agg_layout="bcsr_kernel")
    # a narrowed engine never routes through the edge operands
    eng = GNNServingEngine(model, params, data, num_machines=2, fanout=2,
                           agg_layout="csr")
    assert eng.backend._agg_for_width(eng.backend._width(
        GNNRequest(uid=0, nodes=[0]))) is None
    eng.submit(GNNRequest(uid=0, nodes=[0, 1]))
    assert len(eng.run()) == 1


def test_model_agg_layout_flows_to_serving_default():
    data = sbm_graph(num_nodes=60, feature_dim=8, num_classes=3, seed=2)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=8, agg_layout="csr")
    eng = GNNServingEngine(model, model.init(0), data, num_machines=2)
    assert eng.backend.agg_layout == "csr"


# --------------------------------------------------------------------------
# Operand caching + dtype preservation (the satellite fixes)
# --------------------------------------------------------------------------
def test_operands_are_cached_per_graph():
    data = sbm_graph(num_nodes=90, feature_dim=8, num_classes=3, seed=4)
    g = data.graph
    assert edge_operands(g) is edge_operands(g)
    a1 = build_agg_operands(g, "bcsr_kernel")
    a2 = build_agg_operands(g, "bcsr_kernel")
    assert a1.bcsr is a2.bcsr
    # the kernel wrapper shares the same per-graph BCSR cache
    h = jnp.asarray(data.features)
    spmm_aggregate(g, h)
    cache = g.__dict__["_bcsr_cache"]
    before = len(cache)
    spmm_aggregate(g, h)
    assert len(cache) == before


def test_stacked_edge_operands_pad_rows_drop():
    g1 = sbm_graph(num_nodes=40, feature_dim=4, num_classes=2, seed=0).graph
    g2 = sbm_graph(num_nodes=60, feature_dim=4, num_classes=2, seed=1).graph
    ns = 64
    st = stacked_edge_operands([g1, g2], ns)
    assert st.seg.shape == st.nbr.shape == st.w_mean.shape
    assert st.seg.shape[0] == 2
    # padding edges carry the dropped segment id and zero weight
    e1 = g1.num_edges
    if st.seg.shape[1] > e1:
        assert int(st.seg[0, e1]) == ns
    assert float(st.w_mean[0, e1:].sum()) == 0.0
    # stacked row 0 aggregates exactly like the single-graph operands
    h = jnp.asarray(np.random.default_rng(0).standard_normal(
        (ns, 4)).astype(np.float32))
    single = edge_operands(g1, num_segments=ns)
    row0 = jax.tree_util.tree_map(lambda x: x[0], st)
    from repro.models.gnn.agg import csr_mean_aggregate
    np.testing.assert_allclose(
        np.asarray(csr_mean_aggregate(h, row0)),
        np.asarray(csr_mean_aggregate(h, single)),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_wrappers_preserve_dtype(dtype):
    data = sbm_graph(num_nodes=70, feature_dim=8, num_classes=3, seed=6)
    g = data.graph
    h = jnp.asarray(data.features).astype(dtype)
    assert spmm_aggregate(g, h).dtype == dtype
    agg = build_agg_operands(g, "bcsr_kernel")
    assert L.mean_aggregate(h, None, None, agg=agg).dtype == dtype
    agg_c = build_agg_operands(g, "csr")
    assert L.mean_aggregate(h, None, None, agg=agg_c).dtype == dtype


def test_fused_gat_preserves_dtype(skewed):
    data, table, mask = skewed
    scores = jnp.asarray(np.random.default_rng(0).standard_normal(
        (*table.shape, 2)).astype(np.float32))
    z = jnp.asarray(np.random.default_rng(1).standard_normal(
        (table.shape[0], 6)))
    for dt in (jnp.float32, jnp.bfloat16):
        out = edge_softmax_aggregate(scores.astype(dt), mask, z.astype(dt),
                                     table)
        assert out.dtype == dt
