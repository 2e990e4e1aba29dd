"""Degree-bucketed full-neighbor tables: the single padded table's twin.

The contract under test (``repro.models.gnn.agg.degree_buckets``): every
node's row keeps the single ``(N, max_deg)`` table's slots in the same order,
only all-padding slots go, so the mean and sym aggregations, a GCN/SAGE
model's gradients (also inside the correction's ``lax.scan``) and the plan's
evaluation and correction match the single table to round-off.  A
near-regular graph comes out as one bucket: the single table and its
program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core.engine import EngineConfig, RoundProgram
from repro.core.plan import DistConfig, RoundSampler, build_trainer, llcg_plan
from repro.graph.csr import (
    CSRGraph, build_neighbor_table, symmetric_normalizers,
)
from repro.graph.datasets import grid_graph, rmat_graph, sbm_graph
from repro.models.gnn import layers as L
from repro.models.gnn.agg import (
    AggOperands, DegreeBuckets, bucket_widths, bucketed_operands,
    choose_layout, degree_buckets, full_table_stats,
)
from repro.models.gnn.model import build_model
from repro.optim import make_optimizer


def _skewed_graph() -> CSRGraph:
    """120 nodes: one hub of degree 70, ten isolated nodes, and the rest
    with degrees spread over the 8-, 16- and 24-wide buckets."""
    rng = np.random.default_rng(0)
    src, dst = [np.zeros(70, np.int64)], [np.arange(40, 110)]
    for v, deg in zip(range(1, 40), np.tile([3, 11, 19], 13)):
        nbrs = rng.choice(np.arange(40, 110), size=deg, replace=False)
        src.append(np.full(deg, v))
        dst.append(nbrs)
    # nodes 110..119 keep degree 0
    return CSRGraph.from_edges(120, np.concatenate(src), np.concatenate(dst))


@pytest.fixture(scope="module")
def skewed():
    g = _skewed_graph()
    table, mask = build_neighbor_table(g)
    feats = np.random.default_rng(1).standard_normal((g.num_nodes, 12))
    return g, jnp.asarray(table), jnp.asarray(mask), jnp.asarray(
        feats.astype(np.float32))


def test_bucket_widths_round_up_then_double():
    deg = np.array([0, 1, 8, 9, 17, 25, 32, 33, 64, 65, 1000])
    np.testing.assert_array_equal(
        bucket_widths(deg, 1000),
        [0, 8, 8, 16, 24, 32, 32, 64, 64, 128, 1000])
    # never wider than the single table
    np.testing.assert_array_equal(bucket_widths(np.array([3, 5]), 5), [5, 5])


def test_buckets_keep_each_row_of_the_single_table(skewed):
    g, table, mask, _ = skewed
    deg = g.degrees()
    assert deg.max() == 70 and (deg == 0).sum() == 10
    b = degree_buckets(g)
    widths = [t.shape[1] for t in b.tables]
    assert widths == [0, 8, 16, 24, 70]
    order, slot_of = np.asarray(b.order), np.asarray(b.slot_of)
    np.testing.assert_array_equal(np.sort(order), np.arange(g.num_nodes))
    np.testing.assert_array_equal(order[slot_of], np.arange(g.num_nodes))
    table, mask = np.asarray(table), np.asarray(mask)
    start = 0
    for tab, msk in zip(b.tables, b.masks):
        tab, msk = np.asarray(tab), np.asarray(msk)
        rows = order[start:start + tab.shape[0]]
        w = tab.shape[1]
        np.testing.assert_array_equal(msk, mask[rows, :w])
        assert mask[rows, w:].sum() == 0          # only padding dropped
        np.testing.assert_array_equal(np.where(msk > 0, tab, -1),
                                      np.where(msk > 0, table[rows, :w], -1))
        # pad slots point at the row's own node
        np.testing.assert_array_equal(
            np.where(msk > 0, rows[:, None], tab), rows[:, None]
            * np.ones_like(tab))
        start += tab.shape[0]
    # the builder caches on the graph
    assert degree_buckets(g) is b


@pytest.mark.parametrize("op", ["mean", "sym"])
def test_bucketed_aggregate_matches_single_table(skewed, op):
    g, table, mask, h = skewed
    agg = bucketed_operands(g)
    if op == "mean":
        want = L.mean_aggregate(h, table, mask)
        got = L.mean_aggregate(h, table, mask, agg=agg)
    else:
        nrm = jnp.asarray(symmetric_normalizers(g))
        want = L.sym_aggregate(h, table, mask, nrm)
        got = L.sym_aggregate(h, table, mask, nrm, agg=agg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(got)[110:].any()        # zero-degree rows


@pytest.mark.parametrize("arch", ["GBGL", "SS", "APPNP"])
def test_bucketed_model_gradient_matches_single_table(skewed, arch):
    g, table, mask, h = skewed
    agg = bucketed_operands(g)
    model = build_model(arch, h.shape[1], 4, hidden_dim=16, appnp_steps=3)
    params = model.init(0)

    def loss(p, a):
        return jax.nn.log_softmax(model.apply(p, h, table, mask, agg=a))[
            :64, 0].mean()

    g_one = jax.grad(loss)(params, None)
    g_bkt = jax.jit(jax.grad(loss))(params, agg)
    for want, got in zip(jax.tree_util.tree_leaves(g_one),
                         jax.tree_util.tree_leaves(g_bkt)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_bucketed_correction_scan_matches_single_table(skewed):
    """Three server steps through the engine's correction ``lax.scan``."""
    g, table, mask, h = skewed
    model = build_model("GBG", h.shape[1], 4, hidden_dim=16)
    opt = make_optimizer("adam", 1e-2)
    program = RoundProgram(model, opt, opt,
                           EngineConfig(num_machines=1, with_correction=True))
    params = model.init(0)
    labels = jnp.asarray(np.arange(g.num_nodes) % 4, jnp.int32)
    batches = jnp.asarray(np.random.default_rng(2).integers(
        0, g.num_nodes, (3, 16)), jnp.int32)
    bmasks = jnp.ones((3, 16), jnp.float32)
    out = {}
    for name, agg in (("one", None), ("buckets", bucketed_operands(g))):
        out[name] = program._corr(params, opt.init(params), h, labels, table,
                                  mask, batches, bmasks, agg)
    for want, got in zip(jax.tree_util.tree_leaves(out["one"][:2]),
                         jax.tree_util.tree_leaves(out["buckets"][:2])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(out["buckets"][2]),
                               float(out["one"][2]), rtol=1e-6)


@pytest.mark.parametrize("graph", ["ring", "grid"])
def test_near_regular_graph_keeps_the_single_table(graph):
    """Every degree rounds up to the same width: one bucket in node order,
    whose table is the single table's, and the same numbers to the bit."""
    if graph == "ring":
        n = 50
        g = CSRGraph.from_edges(n, np.arange(n), (np.arange(n) + 1) % n)
    else:
        g = grid_graph(8, num_classes=2, feature_dim=4).graph
    b = degree_buckets(g)
    assert len(b.tables) == 1
    np.testing.assert_array_equal(np.asarray(b.order), np.arange(g.num_nodes))
    table, mask = build_neighbor_table(g)
    np.testing.assert_array_equal(np.asarray(b.masks[0]), mask)
    np.testing.assert_array_equal(
        np.where(mask > 0, np.asarray(b.tables[0]), -1),
        np.where(mask > 0, table, -1))
    assert full_table_stats(g) == {
        "full_agg_slots": g.num_nodes * g.max_degree(),
        "full_agg_edges": g.num_edges, "full_agg_buckets": 1}
    h = jnp.asarray(np.random.default_rng(3).standard_normal(
        (g.num_nodes, 6)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(L.mean_aggregate(h, None, None, agg=bucketed_operands(g))),
        np.asarray(L.mean_aggregate(h, jnp.asarray(table),
                                    jnp.asarray(mask))))


def test_auto_layout_weighs_the_bucket_slots(skewed):
    """``auto`` costs padded by the slots it gathers: the single table's
    ``N·max_deg`` would send this graph to csr, its buckets keep padded."""
    g = skewed[0]
    kw = dict(num_nodes=g.num_nodes, num_edges=g.num_edges,
              width=g.max_degree(), full_width=g.max_degree())
    slots = full_table_stats(g)["full_agg_slots"]
    assert slots < 2.0 * g.num_edges <= g.num_nodes * g.max_degree()
    assert choose_layout("auto", **kw) == "csr"
    assert choose_layout("auto", padded_slots=slots, **kw) == "padded"


# --------------------------------------------------------------------------
# Through the plan: evaluation and correction
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def sbm():
    data = sbm_graph(num_nodes=400, num_classes=4, feature_dim=8,
                     avg_degree=6.9, homophily=0.9, feature_snr=0.3, seed=0)
    model = build_model("GBGBG", data.feature_dim, data.num_classes,
                        hidden_dim=16)
    cfg = DistConfig(num_machines=2, rounds=2, local_k=2, batch_size=16,
                     server_batch_size=32, correction_steps=1, fanout=5,
                     partition_method="random", seed=0)
    return data, model, llcg_plan(cfg)


def _single_table(data):
    table, mask = build_neighbor_table(data.graph)
    return jnp.asarray(table), jnp.asarray(mask)


def test_plan_evaluation_and_correction_match_single_table(sbm):
    data, model, plan = sbm
    sampler = RoundSampler(data, model, plan)
    # GBGBG reads no single table: a zero-width stand-in is all it holds
    assert sampler.full_table_j.shape == (data.num_nodes, 0)
    assert sampler.full_mask_j.shape == (data.num_nodes, 0)
    assert sampler.correction_operands() is sampler.full_agg
    table, mask = _single_table(data)
    params = model.init(1)
    nodes = jnp.asarray(data.val_nodes)
    np.testing.assert_allclose(
        np.asarray(sampler.eval_fn(params, sampler.full_feats,
                                   sampler.full_table_j, sampler.full_mask_j,
                                   sampler.full_labels, nodes,
                                   sampler.full_agg)),
        np.asarray(sampler.eval_fn(params, sampler.full_feats, table, mask,
                                   sampler.full_labels, nodes)),
        rtol=1e-5, atol=1e-5)

    corr = sampler.sample_correction()
    assert corr["corr_agg"] is sampler.full_agg
    program = RoundProgram(model, sampler.opt, sampler.server_opt,
                           EngineConfig(num_machines=2, with_correction=True))
    out = {}
    for name, tm, agg in (("one", (table, mask), None),
                          ("buckets", (corr["corr_tables"],
                                       corr["corr_masks"]), corr["corr_agg"])):
        out[name] = program._corr(
            params, sampler.server_opt.init(params), corr["corr_feats"],
            corr["corr_labels"], *tm, corr["corr_batches"],
            corr["corr_bmasks"], agg)
    for want, got in zip(jax.tree_util.tree_leaves(out["one"]),
                         jax.tree_util.tree_leaves(out["buckets"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_gat_plan_keeps_the_single_table(sbm):
    """GAT's attention reads the degree buckets as the mean aggregations
    do: its plan holds only the zero-width stand-in, and its evaluation on
    the buckets gives the numbers of the single table."""
    data, _, plan = sbm
    model = build_model("GAT", data.feature_dim, data.num_classes,
                        hidden_dim=8)
    sampler = RoundSampler(data, model, plan)
    assert sampler.full_table_j.shape == (data.num_nodes, 0)
    table, mask = _single_table(data)
    head = (model.init(0), sampler.full_feats)
    tail = (sampler.full_labels, jnp.asarray(data.val_nodes))
    np.testing.assert_allclose(
        np.asarray(sampler.eval_fn(*head, sampler.full_table_j,
                                   sampler.full_mask_j, *tail,
                                   sampler.full_agg)),
        np.asarray(sampler.eval_fn(*head, table, mask, *tail)),
        rtol=1e-5, atol=1e-6)


def test_plan_auto_layout_keeps_the_buckets():
    """On a skewed graph whose single table would send ``auto`` to csr, the
    correction resolves to padded and runs on the buckets."""
    data = rmat_graph(num_nodes=160, num_edges=700, feature_dim=10,
                      num_classes=4, seed=5)
    g = data.graph
    assert g.num_nodes * g.max_degree() >= 2.0 * g.num_edges
    model = build_model("GGL", data.feature_dim, data.num_classes,
                        hidden_dim=8)
    cfg = DistConfig(num_machines=2, rounds=1, local_k=2, batch_size=16,
                     server_batch_size=16, correction_steps=1, fanout=5,
                     partition_method="random", server_agg_layout="auto",
                     seed=0)
    sampler = RoundSampler(data, model, llcg_plan(cfg))
    assert sampler.corr_agg_layout == "padded"
    assert sampler.correction_operands() is sampler.full_agg


def test_plan_counts_full_aggregation_slots(sbm, monkeypatch):
    data, model, plan = sbm
    deg = data.graph.degrees()
    assert deg.max() <= 32                  # multiples of 8 only
    width = np.minimum(-(-deg // 8) * 8, deg.max())
    hist = build_trainer(data, model, plan).run()
    assert hist.meta["full_agg_slots"] == int(width.sum())
    assert hist.meta["full_agg_slots"] < deg.size * deg.max()
    assert hist.meta["full_agg_edges"] == int(deg.sum())
    assert hist.meta["full_agg_buckets"] == np.unique(width).size

    # the same plan on the single table, as one bucket of every node in
    # node order: same evaluation and correction
    table, mask = _single_table(data)
    n = np.arange(data.num_nodes, dtype=np.int32)
    single = AggOperands("bucketed", buckets=DegreeBuckets(
        tables=(table,), masks=(mask,), order=jnp.asarray(n),
        slot_of=jnp.asarray(n)))
    monkeypatch.setattr(plan_mod, "bucketed_operands", lambda g: single)
    one = build_trainer(data, model, plan).run()
    np.testing.assert_allclose(hist.train_loss, one.train_loss, rtol=1e-5)
    np.testing.assert_allclose(hist.val_score, one.val_score, rtol=1e-5)
    np.testing.assert_allclose(hist.meta["corr_loss"], one.meta["corr_loss"],
                               rtol=1e-5)
