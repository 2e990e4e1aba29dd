"""The model's last ops on the loss's rows alone (``GNNModel.apply`` with
``rows``) against the whole forward and then the rows.

Every architecture kind is covered: the last aggregation cut (``GBGBG``,
``SBSBL``, the GAT stack with and without the kernel), a last BatchNorm
after the last aggregation (``BSBSBL``), and APPNP's propagation.  Each runs
over a sampled padded table, over the degree buckets with the rows' slots
gathered on the device, and over the rows' own prebuilt buckets.  The
batch holds a repeated row and padded entries (``bmask`` 0).  Logits,
losses and gradients must match the whole forward to 1e-6 of the largest
value: the same arithmetic, summed in another order at most.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import machine
from repro.core.machine import make_eval_fn, make_local_round, make_loss_fn
from repro.core.plan import DistConfig, build_trainer, llcg_plan
from repro.graph.csr import CSRGraph
from repro.graph.datasets import sbm_graph
from repro.graph.sampling import sample_neighbors
from repro.models.gnn import build_model
from repro.models.gnn.agg import (
    build_agg_operands, bucketed_operands, degree_buckets, row_slots,
)
from repro.optim import make_optimizer

N, FEAT, CLASSES = 120, 12, 5
GAT = dict(num_heads=3, num_layers=3, residual=True, self_loop=True,
           batch_norm=True)
MODELS = {"GBGBG": ("GBGBG", {}), "BSBSBL": ("BSBSBL", {}),
          "SBSBL": ("SBSBL", {}), "APPNP": ("APPNP", {}),
          "GAT": ("GAT", GAT), "GAT-kernel": ("GAT", dict(GAT,
                                                         fused_gat=True))}
TABLES = ("sampled", "buckets", "row_buckets")
# a repeated row (7), a zero-degree node (119) and two padded entries
BATCH = np.array([7, 3, 7, 119, 60, 0, 0], np.int32)
BMASK = np.array([1, 1, 1, 1, 1, 0, 0], np.float32)
TOL = 1e-6


def _graph() -> CSRGraph:
    """A hub of degree 70, nodes of degree 3, 11 and 19, and ten isolated
    nodes: buckets 8, 16, 24 and 70 wide besides the zero-degree one."""
    rng = np.random.default_rng(0)
    src, dst = [np.zeros(70, np.int64)], [np.arange(40, 110)]
    for v, deg in zip(range(1, 40), np.tile([3, 11, 19], 13)):
        src.append(np.full(deg, v))
        dst.append(rng.choice(np.arange(40, 110), size=deg, replace=False))
    return CSRGraph.from_edges(N, np.concatenate(src), np.concatenate(dst))


@pytest.fixture(scope="module")
def graph():
    g = _graph()
    assert g.degrees()[119] == 0
    assert len(degree_buckets(g).tables) >= 4
    return g


@pytest.fixture(scope="module")
def inputs(graph):
    rng = np.random.default_rng(1)
    feats = jnp.asarray(rng.standard_normal((N, FEAT)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, CLASSES, N).astype(np.int32))
    table, mask = sample_neighbors(graph, np.arange(N), 6,
                                   np.random.default_rng(2))
    return feats, labels, jnp.asarray(table), jnp.asarray(mask)


def _model(name):
    arch, kw = MODELS[name]
    return build_model(arch, FEAT, CLASSES, hidden_dim=8, **kw)


def _operands(kind, graph, inputs):
    """(table, mask, agg, rows) of a table kind, the rows being BATCH."""
    _, _, table, mask = inputs
    if kind == "sampled":
        return table, mask, None, jnp.asarray(BATCH)
    empty = (jnp.zeros((N, 0), jnp.int32), jnp.zeros((N, 0), jnp.float32))
    rows = (jnp.asarray(BATCH) if kind == "buckets"
            else degree_buckets(graph, BATCH))
    return (*empty, bucketed_operands(graph), rows)


def _close(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape
        assert np.max(np.abs(x - y)) <= TOL * max(np.max(np.abs(y)), 1e-30)


def _full_loss(model):
    """The loss as the whole forward computes it: every row's logits,
    then the batch's."""
    def loss(params, feats, table, mask, batch, labels, bmask, agg=None):
        lg = model.apply(params, feats, table, mask, agg=agg)[batch]
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[batch][:, None], axis=-1)
        return (nll[:, 0] * bmask).sum() / jnp.clip(bmask.sum(), 1.0, None)
    return loss


@pytest.mark.parametrize("kind", TABLES)
@pytest.mark.parametrize("name", list(MODELS))
def test_rows_match_the_whole_forward(name, kind, graph, inputs):
    model = _model(name)
    params = model.init(0)
    feats, labels, _, _ = inputs
    table, mask, agg, rows = _operands(kind, graph, inputs)
    whole = model.apply(params, feats, table, mask, agg=agg)
    part = model.apply(params, feats, table, mask, agg=agg, rows=rows)
    assert part.shape == (BATCH.size, CLASSES)
    _close(part, whole[BATCH])

    args = (feats, table, mask, jnp.asarray(BATCH), labels,
            jnp.asarray(BMASK), agg)
    new = jax.value_and_grad(make_loss_fn(model))(params, *args)
    old = jax.value_and_grad(_full_loss(model))(params, *args)
    _close(new, old)


def test_rows_on_the_csr_layout_select_after_the_aggregation(graph, inputs):
    """The ``csr`` layout carries no slots for rows: the rows are taken
    after the last aggregation, with the same logits."""
    feats, _, table, mask = inputs
    agg = build_agg_operands(graph, "csr")
    for name in ("GBGBG", "GAT"):
        model = _model(name)
        params = model.init(0)
        whole = model.apply(params, feats, table, mask, agg=agg)
        _close(model.apply(params, feats, table, mask, agg=agg,
                           rows=jnp.asarray(BATCH)), whole[BATCH])


def test_row_slots_gathered_on_the_device_are_the_rows_buckets(graph):
    """The rows' slots gathered out of the degree buckets hold each row's
    real neighbours in the buckets' slot order, padding after them."""
    slots = row_slots(jnp.asarray(BATCH), None, None,
                      bucketed_operands(graph))
    (tab,), (msk,) = slots.tables, slots.masks
    assert tab.shape == (BATCH.size, max(graph.degrees()))
    for r, v in enumerate(BATCH):
        deg = int(graph.degrees()[v])
        np.testing.assert_array_equal(np.asarray(tab[r, :deg]),
                                      graph.neighbors(v))
        assert np.asarray(msk[r]).sum() == deg


@pytest.mark.parametrize("name", ["GBGBG", "GAT-kernel"])
def test_vmapped_local_round_matches_the_whole_forward(name, graph, inputs,
                                                       monkeypatch):
    """Two machines' K=2 local steps under vmap: the rows' forward and the
    whole forward end at the same parameters and losses."""
    model = _model(name)
    params = model.init(0)
    feats, labels, table, mask = inputs
    rng = np.random.default_rng(3)
    tables = jnp.stack([jnp.stack([table, jnp.roll(table, 1, 0)])] * 2)
    masks = jnp.stack([jnp.stack([mask, jnp.roll(mask, 1, 0)])] * 2)
    batches = jnp.asarray(rng.integers(0, N, (2, 2, BATCH.size)), jnp.int32)
    bmasks = jnp.broadcast_to(jnp.asarray(BMASK), batches.shape)
    svalid = jnp.ones((2,), jnp.float32)
    opt = make_optimizer("adam", 0.01)

    def run(local_round):
        return jax.vmap(lambda f, l, t, m, b, bm: local_round(
            params, None, f, l, t, m, b, bm, svalid))(
            jnp.stack([feats] * 2), jnp.stack([labels] * 2), tables, masks,
            batches, bmasks)

    new = run(make_local_round(model, opt))
    monkeypatch.setattr(machine, "make_loss_fn", _full_loss)
    old = run(make_local_round(model, opt))
    _close((new[0], new[2]), (old[0], old[2]))


@pytest.mark.parametrize("name", ["GBGBG", "GAT"])
def test_evaluation_on_the_rows_buckets(name, graph, inputs):
    """The evaluation over the validation rows' own buckets gives the
    whole forward's loss and score over those rows."""
    model = _model(name)
    params = model.init(0)
    feats, labels, _, _ = inputs
    _, _, agg, _ = _operands("buckets", graph, inputs)
    empty = (jnp.zeros((N, 0), jnp.int32), jnp.zeros((N, 0), jnp.float32))
    nodes = np.arange(1, N, 3)
    loss, score = make_eval_fn(model)(params, feats, *empty, labels,
                                      degree_buckets(graph, nodes), agg)
    logits = model.apply(params, feats, *empty, agg=agg)[nodes]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ref = -jnp.take_along_axis(logp, labels[nodes][:, None], axis=-1).mean()
    _close(loss, ref)
    assert float(score) == float(
        (logits.argmax(-1) == labels[nodes]).mean())


@pytest.mark.parametrize("arch,cut", [("GBGBG", 4), ("BSBSBL", "none"),
                                      ("GAT", 1)])
def test_history_counts_the_loss_rows(arch, cut):
    """``History.meta`` names the rows the loss reads per local step (all
    machines), per correction step and in the evaluation, and the op
    where the cut starts."""
    data = sbm_graph(num_nodes=160, num_classes=CLASSES, feature_dim=FEAT,
                     avg_degree=3.0, seed=0)
    cfg = DistConfig(num_machines=2, rounds=1, local_k=2, batch_size=16,
                     server_batch_size=24, correction_steps=1, fanout=4,
                     partition_method="random", seed=0)
    model = build_model(arch, FEAT, CLASSES, hidden_dim=8)
    hist = build_trainer(data, model, llcg_plan(cfg)).run()
    assert hist.meta["top_rows_local"] == 2 * 16
    assert hist.meta["top_rows_correction"] == 24
    assert hist.meta["top_rows_eval"] == data.val_nodes.size
    assert hist.meta["top_cut_op"] == cut
    assert np.all(np.isfinite(hist.val_score))
