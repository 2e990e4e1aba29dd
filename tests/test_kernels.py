"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # deterministic fallback, see hypothesis_compat
    from hypothesis_compat import given, settings, st

from repro.graph import sbm_graph, rmat_graph
from repro.graph.csr import build_neighbor_table
from repro.kernels import ref
from repro.kernels.ops import (
    edge_softmax_aggregate, linear_scan, pallas_interpret, spmm_aggregate,
)
from repro.kernels.spmm import build_bcsr, spmm_bcsr
from repro.models.gnn.layers import mean_aggregate


# --------------------------------------------------------------------------
# SpMM
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,d,seed", [(100, 16, 0), (257, 20, 1), (300, 64, 2)])
def test_spmm_matches_mean_aggregate(n, d, seed):
    ds = sbm_graph(num_nodes=n, feature_dim=d, seed=seed)
    h = jnp.asarray(ds.features)
    out_k = spmm_aggregate(ds.graph, h, normalization="mean")
    tab, msk = build_neighbor_table(ds.graph)
    out_r = mean_aggregate(h, jnp.asarray(tab), jnp.asarray(msk))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("norm", ["mean", "sym", "none"])
def test_spmm_bcsr_matches_dense(norm):
    ds = rmat_graph(num_nodes=200, num_edges=1500, feature_dim=32, seed=3)
    cols, vals, n_pad = build_bcsr(ds.graph, block_m=8, block_n=128,
                                   normalization=norm)
    h = jnp.asarray(np.random.default_rng(0).standard_normal(
        (n_pad, 128)).astype(np.float32))
    out_k = spmm_bcsr(jnp.asarray(cols), jnp.asarray(vals), h, block_d=128,
                      interpret=pallas_interpret())
    out_r = ref.spmm_bcsr_ref(jnp.asarray(cols), jnp.asarray(vals), h)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


def test_spmm_bcsr_reconstructs_dense_matmul():
    """BCSR path == dense Â @ H computed naively."""
    ds = sbm_graph(num_nodes=96, feature_dim=8, seed=5)
    n = ds.graph.num_nodes
    dense = np.zeros((n, n), np.float32)
    deg = np.maximum(ds.graph.degrees(), 1)
    src, dst = ds.graph.to_edges()
    dense[src, dst] = 1.0 / deg[src]
    h = ds.features
    expect = dense @ h
    got = spmm_aggregate(ds.graph, jnp.asarray(h), normalization="mean")
    np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# Edge softmax
# --------------------------------------------------------------------------
def _attention_case(rng, n, f, d, heads, dtype=jnp.float32):
    """Scores (n, f, heads), mask (n, f), z (n + 5, heads·d) and a table of
    ids into z's rows."""
    scores = jnp.asarray(rng.standard_normal((n, f, heads)), dtype)
    mask = jnp.asarray((rng.random((n, f)) > 0.3).astype(np.float32))
    z = jnp.asarray(rng.standard_normal((n + 5, heads * d)), dtype)
    table = jnp.asarray(rng.integers(0, n + 5, (n, f)), jnp.int32)
    return scores, mask, z, table


@given(n=st.integers(4, 200), f=st.integers(1, 24), d=st.integers(1, 70),
       seed=st.integers(0, 99))
@settings(max_examples=12, deadline=None)
def test_edge_softmax_matches_ref(n, f, d, seed):
    """The kernel forward (rows gathered in the kernel) and the chunked
    XLA forward against the oracle, 1 to 3 heads."""
    rng = np.random.default_rng(seed)
    args = _attention_case(rng, n, f, d, heads=1 + seed % 3)
    want = ref.edge_softmax_ref(*args)
    for fused in (True, False):
        got = edge_softmax_aggregate(*args, fused=fused)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_edge_softmax_fully_masked_rows_are_zero():
    scores = jnp.zeros((8, 4, 2), jnp.float32)
    mask = jnp.zeros((8, 4), jnp.float32)
    z = jnp.ones((8, 32), jnp.float32)
    table = jnp.zeros((8, 4), jnp.int32)
    for fused in (True, False):
        out = edge_softmax_aggregate(scores, mask, z, table, fused=fused)
        np.testing.assert_allclose(np.asarray(out), 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_edge_softmax_dtypes(dtype):
    rng = np.random.default_rng(1)
    args = _attention_case(rng, 32, 8, 12, heads=2, dtype=dtype)
    want = ref.edge_softmax_ref(*args)
    for fused in (True, False):
        got = edge_softmax_aggregate(*args, fused=fused)
        assert got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=2e-2, atol=2e-2)


# --------------------------------------------------------------------------
# Linear scan (Mamba2 / RWKV6 core)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("bh,t,dk,dv,chunk", [
    (2, 64, 8, 16, 16), (3, 128, 16, 24, 32), (1, 96, 32, 32, 32),
    (4, 256, 64, 64, 64), (2, 100, 16, 16, 32),
])
def test_linear_scan_kernel_matches_sequential_ref(bh, t, dk, dv, chunk):
    rng = np.random.default_rng(bh + t)
    q = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, t, dv)), jnp.float32)
    lw = jnp.asarray(-0.15 * rng.random((bh, t, dk)), jnp.float32)
    y_k, h_k = linear_scan(q, k, v, lw, chunk=chunk)
    y_r, h_r = ref.linear_scan_batched_ref(q, k, v, lw)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=2e-4, atol=2e-4)


def test_linear_scan_with_initial_state():
    rng = np.random.default_rng(9)
    bh, t, dk, dv = 2, 32, 8, 8
    q = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, t, dv)), jnp.float32)
    lw = jnp.asarray(-0.1 * rng.random((bh, t, dk)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((bh, dk, dv)), jnp.float32)
    y_k, h_k = linear_scan(q, k, v, lw, h0=h0, chunk=16)
    y_r, h_r = ref.linear_scan_batched_ref(q, k, v, lw, h0=h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=2e-4, atol=2e-4)


def test_linear_scan_chunk_invariance():
    """Different chunk sizes must agree (associativity of the recurrence)."""
    rng = np.random.default_rng(11)
    bh, t, dk, dv = 2, 128, 16, 16
    q = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, t, dv)), jnp.float32)
    lw = jnp.asarray(-0.2 * rng.random((bh, t, dk)), jnp.float32)
    outs = [linear_scan(q, k, v, lw, chunk=c)[0] for c in (16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# chunked_scan (jnp path) — strict/RWKV6 variant
# --------------------------------------------------------------------------
def test_chunked_scan_strict_matches_stepwise():
    from repro.models.transformer.scan_common import chunked_scan, scan_decode_step
    rng = np.random.default_rng(21)
    bh, t, dk, dv = 2, 48, 8, 8
    q = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, t, dv)), jnp.float32)
    lw = jnp.asarray(-0.1 * rng.random((bh, t, dk)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((bh, dk)), jnp.float32)
    y_c, h_c = chunked_scan(q, k, v, lw, chunk=16, strict=True, u=u)
    # stepwise oracle
    h = jnp.zeros((bh, dk, dv), jnp.float32)
    ys = []
    for i in range(t):
        y, h = scan_decode_step(q[:, i], k[:, i], v[:, i], lw[:, i], h,
                                strict=True, u=u)
        ys.append(y)
    y_s = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# Fused GAT path: kernel forward + chunked XLA backward == plain JAX exactly
# --------------------------------------------------------------------------
def test_fused_gat_layer_matches_plain_forward_and_grad():
    from repro.graph.csr import build_neighbor_table
    from repro.models.gnn import build_model

    ds = sbm_graph(num_nodes=150, feature_dim=12, seed=4)
    tab, msk = build_neighbor_table(ds.graph, max_deg=8)
    plain = build_model("GAT", ds.feature_dim, ds.num_classes, hidden_dim=16)
    fused = build_model("GAT", ds.feature_dim, ds.num_classes, hidden_dim=16,
                        fused_gat=True)
    params = plain.init(0)
    x = jnp.asarray(ds.features)
    t, m = jnp.asarray(tab), jnp.asarray(msk)
    np.testing.assert_allclose(np.asarray(plain.apply(params, x, t, m)),
                               np.asarray(fused.apply(params, x, t, m)),
                               rtol=1e-5, atol=1e-5)

    def loss(mdl):
        return lambda p: jnp.mean((mdl.apply(p, x, t, m) - 1.0) ** 2)

    g_plain = jax.grad(loss(plain))(params)
    g_fused = jax.grad(loss(fused))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_plain),
                    jax.tree_util.tree_leaves(g_fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_linear_scan_strict_kernel_matches_stepwise():
    """The Pallas kernel's strict (RWKV6) variant vs the stepwise oracle."""
    from repro.models.transformer.scan_common import scan_decode_step
    rng = np.random.default_rng(31)
    bh, t, dk, dv = 2, 64, 16, 16
    q = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, t, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, t, dv)), jnp.float32)
    lw = jnp.asarray(-0.12 * rng.random((bh, t, dk)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((bh, dk)), jnp.float32)
    y_k, h_k = linear_scan(q, k, v, lw, chunk=16, strict=True, u=u)
    h = jnp.zeros((bh, dk, dv), jnp.float32)
    ys = []
    for i in range(t):
        y, h = scan_decode_step(q[:, i], k[:, i], v[:, i], lw[:, i], h,
                                strict=True, u=u)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(jnp.stack(ys, 1)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h),
                               rtol=2e-4, atol=2e-4)
