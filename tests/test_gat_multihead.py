"""The multi-head residual GAT stack against a plain float32 reference of its
equations, on the padded, degree-bucketed and sampled tables, through the
Pallas kernel (interpret mode) and the chunked XLA path.

The reference here is dense: every row attends over an ``(N, N)``
adjacency, so it shares no gather, table or bucket with the program.
Layer l, H heads of width F: ``z = h W``; ``e[i, j] = LeakyReLU_0.2(z_i·a_dst
+ z_j·a_src)`` over j in N(i) ∪ {i}; ``o = softmax_j(e) z + h R``; between
layers ReLU(BatchNorm(concat)), at the last the heads' mean + b.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ops as ops
from repro.core.plan import DistConfig, build_trainer, llcg_plan
from repro.graph.csr import CSRGraph, build_neighbor_table
from repro.graph.datasets import sbm_graph
from repro.kernels import ref
from repro.kernels.ops import attend, edge_softmax_aggregate
from repro.models.gnn import build_model
from repro.models.gnn.agg import bucketed_operands, degree_buckets

N, FEAT, CLASSES, HEADS, F = 300, 12, 5, 3, 8
STACK = dict(num_heads=HEADS, num_layers=3, residual=True, self_loop=True,
             batch_norm=True)
# float32 against float32 over three layers with BatchNorm: the program and
# the dense reference sum in different orders (buckets, slots, heads), so a
# logit moves by a few ulps per layer; 2e-5 of a logit of magnitude ~3 is
# ~40 ulps.  A bfloat16 reference misses this by orders of magnitude
# (test_bf16_reference_fails_the_tolerance).
RTOL, ATOL = 2e-5, 2e-5


def _graph() -> CSRGraph:
    """A directed graph: node 0 has no neighbours, the others 1..40, so the
    buckets are 8-, 16-, 24-, 32- and 40-wide besides the zero-degree
    one."""
    rng = np.random.default_rng(0)
    src, dst = [], []
    for v in range(1, N):
        deg = int(rng.integers(1, 41))
        nbrs = rng.choice(np.delete(np.arange(N), v), size=deg,
                          replace=False)
        src.append(np.full(deg, v))
        dst.append(nbrs)
    return CSRGraph.from_edges(N, np.concatenate(src), np.concatenate(dst),
                               symmetrize=False)


@pytest.fixture(scope="module")
def graph():
    g = _graph()
    assert g.degrees()[0] == 0 and g.max_degree() == 40
    assert len({t.shape[1] for t in degree_buckets(g).tables}) >= 3
    return g


@pytest.fixture(scope="module")
def feats():
    return jnp.asarray(np.random.default_rng(1).standard_normal(
        (N, FEAT)).astype(np.float32))


def _model(**kw):
    return build_model("GAT", FEAT, CLASSES, hidden_dim=F,
                       **dict(STACK, **kw))


def dense_reference(params, h, adj, dtype=jnp.float32):
    """The equations over a dense ``(N, N)`` 0/1 adjacency (self included
    by the caller), every product at ``highest``."""
    cast = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    h, adj = cast(h), cast(adj)
    n = h.shape[0]
    with jax.default_matmul_precision("highest"):
        for layer in range(3):
            p = jax.tree_util.tree_map(cast, params[f"gat{layer}"])
            z = (h @ p["w"]).reshape(n, HEADS, -1)
            s_src = jnp.einsum("nhf,hf->nh", z, p["a_src"])
            s_dst = jnp.einsum("nhf,hf->nh", z, p["a_dst"])
            e = jax.nn.leaky_relu(s_dst[:, None, :] + s_src[None, :, :],
                                  0.2)                              # (i,j,h)
            e = jnp.where(adj[..., None] > 0, e, -jnp.inf)
            alpha = jax.nn.softmax(e, axis=1)
            o = jnp.einsum("ijh,jhf->ihf", alpha, z)
            o = o + (h @ p["r"]).reshape(n, HEADS, -1)
            if layer == 2:
                return o.mean(axis=1) + p["b"]
            o = o.reshape(n, -1)
            bn = jax.tree_util.tree_map(cast, params[f"bn{layer}"])
            mu = o.mean(0)
            var = ((o - mu) ** 2).mean(0)
            h = jax.nn.relu((o - mu) / jnp.sqrt(var + 1e-5) * bn["gamma"]
                            + bn["beta"])


def _adjacency(table, mask):
    adj = np.zeros((N, N), np.float32)
    t, m = np.asarray(table), np.asarray(mask)
    rows = np.repeat(np.arange(N), t.shape[1])
    adj[rows[m.ravel() > 0], t.ravel()[m.ravel() > 0]] = 1.0
    adj[np.arange(N), np.arange(N)] = 1.0               # the self slot
    return adj


def _sampled(graph, fanout=10):
    """A sampled-style table: each row's first ``fanout`` neighbours."""
    table, mask = build_neighbor_table(graph)
    return table[:, :fanout], mask[:, :fanout]


def _loss(logits):
    return jnp.mean(jnp.sin(logits) * logits)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("path", ["padded", "bucketed", "sampled"])
def test_stack_matches_dense_reference(graph, feats, path, fused):
    model = _model(fused_gat=fused)
    params = model.init(3)
    table, mask = (_sampled(graph) if path == "sampled"
                   else build_neighbor_table(graph))
    agg = bucketed_operands(graph) if path == "bucketed" else None
    t, m = jnp.asarray(table), jnp.asarray(mask)
    adj = jnp.asarray(_adjacency(table, mask))

    @jax.jit
    def prog(p):
        """Logits and the gradient of the loss, from one forward."""
        out, vjp = jax.vjp(lambda q: model.apply(q, feats, t, m, agg=agg), p)
        return out, vjp(jax.grad(_loss)(out))[0]

    def want(p):
        return dense_reference(p, feats, adj)

    logits, g_prog = prog(params)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want(params)),
                               rtol=RTOL, atol=ATOL)
    g_want = jax.grad(lambda p: _loss(want(p)))(params)
    assert set(g_prog) == set(g_want)
    for layer in g_want:
        for k in g_want[layer]:
            a, b = np.asarray(g_prog[layer][k]), np.asarray(g_want[layer][k])
            scale = max(float(np.abs(b).max()), 1e-6)
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * scale,
                                       err_msg=f"{layer}/{k}")


def test_bf16_reference_fails_the_tolerance(graph, feats):
    """The tolerances above catch a precision below float32."""
    model = _model()
    params = model.init(3)
    table, mask = build_neighbor_table(graph)
    adj = jnp.asarray(_adjacency(table, mask))
    got = dense_reference(params, feats, adj, dtype=jnp.bfloat16)
    want = dense_reference(params, feats, adj)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=RTOL, atol=ATOL)


def test_parameters_follow_the_documented_order():
    """Per layer w, r, a_dst, a_src from one ``default_rng(seed)``; (H, F)
    attention vectors; BatchNorm between layers; a zero last bias."""
    model = _model()
    p = model.init(7)
    rng = np.random.default_rng(7)
    d = FEAT
    for layer, f in enumerate((F, F, CLASSES)):
        for k, shape in (("w", (d, HEADS * f)), ("r", (d, HEADS * f)),
                         ("a_dst", (HEADS, f)), ("a_src", (HEADS, f))):
            scale = np.sqrt(2.0 / (shape[0] + shape[-1]))
            want = (rng.standard_normal(shape) * scale).astype(np.float32)
            np.testing.assert_array_equal(np.asarray(p[f"gat{layer}"][k]),
                                          want)
        d = HEADS * f
    assert set(p) == {"gat0", "gat1", "gat2", "bn0", "bn1"}
    assert "b" not in p["gat0"] and np.all(np.asarray(p["gat2"]["b"]) == 0)
    np.testing.assert_array_equal(np.asarray(p["bn1"]["gamma"]), 1.0)


def test_default_gat_is_the_two_layer_single_head_formula(graph, feats):
    """The defaults: two layers, one head, no residual, no BatchNorm, no
    self slot; ELU between layers with a bias."""
    model = build_model("GAT", FEAT, CLASSES, hidden_dim=F)
    assert (model.num_layers, model.num_heads) == (2, 1)
    params = model.init(0)
    assert set(params["gat0"]) == {"w", "a_src", "a_dst", "b"}
    table, mask = build_neighbor_table(graph)
    t, m = jnp.asarray(table), jnp.asarray(mask)

    def layer(p, h):
        z = h @ p["w"]
        e = jax.nn.leaky_relu((z @ p["a_dst"])[:, None]
                              + (z @ p["a_src"])[t], 0.2)
        alpha = jax.nn.softmax(jnp.where(m > 0, e, -1e30), axis=-1) * m
        return jnp.einsum("nf,nfd->nd", alpha, z[t]) + p["b"]

    want = layer(params["gat1"], jax.nn.elu(layer(params["gat0"], feats)))
    np.testing.assert_allclose(np.asarray(model.apply(params, feats, t, m)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(want[0]).max()) == pytest.approx(
        float(jnp.abs(params["gat1"]["b"]).max()))  # no neighbour: bias only


def test_kernel_matches_the_oracle_with_a_row_of_no_neighbour():
    rng = np.random.default_rng(5)
    scores = jnp.asarray(rng.standard_normal((40, 9, HEADS)), jnp.float32)
    mask = (rng.random((40, 9)) > 0.3).astype(np.float32)
    mask[7] = 0.0
    z = jnp.asarray(rng.standard_normal((60, HEADS * 20)), jnp.float32)
    table = jnp.asarray(rng.integers(0, 60, (40, 9)), jnp.int32)
    got = edge_softmax_aggregate(scores, jnp.asarray(mask), z, table,
                                 fused=True)
    want = ref.edge_softmax_ref(scores, jnp.asarray(mask), z, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[7]), 0.0)


@pytest.mark.parametrize("fused", [True, False])
def test_chunked_attention_matches_one_chunk(monkeypatch, fused):
    """With chunks of a few rows, the XLA forward and the backward give the
    one-chunk numbers."""
    rng = np.random.default_rng(6)
    r, w = 50, 7
    alpha = jax.nn.softmax(jnp.asarray(rng.standard_normal((r, w, HEADS)),
                                       jnp.float32), axis=1)
    z = jnp.asarray(rng.standard_normal((80, HEADS * 10)), jnp.float32)
    table = jnp.asarray(rng.integers(0, 80, (r, w)), jnp.int32)
    cot = jnp.asarray(rng.standard_normal((r, HEADS * 10)), jnp.float32)

    def run():
        out, vjp = jax.vjp(lambda z, a: attend(z, a, table, fused), z, alpha)
        return (out, *vjp(cot))

    whole = run()
    row_bytes = w * HEADS * 10 * 4
    monkeypatch.setattr(ops, "ATTENTION_CHUNK_BYTES", 6 * row_bytes)
    assert ops.attention_chunk_rows(w, HEADS * 10) == 6
    chunked = run()
    for a, b in zip(chunked, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
def test_stack_trains_through_the_plan(backend):
    """``build_trainer(...).run()`` on both backends, the kernel forward
    under the machines' vmap and under shard_map, with the attention's
    engagement in ``History.meta``."""
    from jax.sharding import Mesh
    data = sbm_graph(num_nodes=160, num_classes=CLASSES, feature_dim=FEAT,
                     avg_degree=3.0, seed=0)
    machines = 2 if backend == "vmap" else 1
    cfg = DistConfig(num_machines=machines, rounds=1, local_k=2,
                     batch_size=16, server_batch_size=32,
                     correction_steps=1, fanout=4, partition_method="random",
                     seed=0)
    mesh = (Mesh(np.asarray(jax.devices()[:1]), ("machine",))
            if backend == "shard_map" else None)
    hist = build_trainer(data, _model(fused_gat=True), llcg_plan(cfg),
                         backend=backend, mesh=mesh).run()
    assert np.all(np.isfinite(hist.meta["local_loss"]))
    assert np.all(np.isfinite(hist.train_loss))
    def slots(buckets):
        return sum(t.shape[0] * (t.shape[1] + 1) for t in buckets.tables)

    full = degree_buckets(data.graph)
    width = max(t.shape[1] for t in full.tables) + 1
    # the last layer attends for the loss's rows alone: the correction
    # batch's at the widest bucket's width, the validation rows' buckets
    val = degree_buckets(data.graph, data.val_nodes)
    assert hist.meta["gat_server_slots"] == (
        2 * slots(full) + 32 * width + 2 * slots(full) + slots(val))
    assert hist.meta["gat_local_slots"] > 0
    assert hist.meta["gat_chunk_bytes"] == ops.ATTENTION_CHUNK_BYTES
    assert hist.meta["gat_kernel"] is True
