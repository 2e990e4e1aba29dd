"""Device-resident round sampling (SamplerSpec placement="device").

1. Raw sampler invariants: every sampled table entry is a true neighbor,
   valid slots are a without-replacement subset, rows with degree ≤ fanout
   keep ALL neighbors, masked slots are zeroed, and batches come from the
   train pool (WOR when it is large enough).
2. The documented key stream: deterministic replay, per-round independence,
   and the K-bucketing anchor — drawing at a padded length reproduces the
   unpadded draw bit-for-bit on the real-step prefix (per-step key folding
   makes each step's draws independent of the scan length).
3. Plan-level differentials: device overlap == device synchronous bit-for-
   bit, host+overlap == host default bit-for-bit (the draw ORDER is
   unchanged, only the float point moves), placement="device" adds no NEW
   round-program compiles under K-bucketing and the sampler itself compiles
   once per (kind, bucket), rng_compat+device is rejected, and the hybrid-
   plan prewarm caches every (graph, fanout) sampling plan before round 1.
4. Serving: device tables at full width reproduce the host path's exact
   full-neighbor predictions and replay deterministically per wave content.
5. The row-table select: tables, masks and batches equal, bit for bit, a
   reference that gathers the selected slots out of the CSR indices, on the
   pairwise-rank path (dmax <= 128) and on the top_k path (dmax > 128).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CommSpec, CompileSpec, LocalSpec, RoundSampler, SamplerSpec,
    ScheduleSpec, ServerSpec, TrainPlan, averaging, build_trainer,
    correction, halo_exchange, local_steps, lower_plan,
)
from repro.graph import build_device_csr, sample_round_device, sbm_graph
from repro.graph.csr import CSRGraph
from repro.graph.sampling import sample_serving_tables_device
from repro.models.gnn import build_model
from repro.serving import GNNRequest, GNNServingEngine


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module")
def tiny():
    data = sbm_graph(num_nodes=160, num_classes=3, feature_dim=8,
                     feature_snr=0.4, homophily=0.9, avg_degree=8, seed=1)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=16)
    return data, model


def _plan(placement="host", overlap=None, bucketing=False, rounds=4,
          rho=1.5, phases=None, rng_compat=False, seed=3):
    return TrainPlan(
        phases=phases or (local_steps(), averaging(), correction()),
        local=LocalSpec(local_k=2, batch_size=8),
        server=ServerSpec(correction_steps=1, server_batch_size=16),
        comm=CommSpec(num_machines=2, partition_method="random"),
        sampler=SamplerSpec(fanout=5, placement=placement, overlap=overlap),
        schedule=ScheduleSpec(rounds=rounds, rho=rho),
        compile=CompileSpec(k_bucketing=bucketing, rng_compat=rng_compat),
        seed=seed)


# --------------------------------------------------------------------------
# 1. raw sampler invariants
# --------------------------------------------------------------------------
def test_device_tables_are_uniform_neighbor_subsets(tiny):
    data, _ = tiny
    fanout, K, B = 4, 3, 8
    train = data.train_nodes.astype(np.int64)
    dcsr = build_device_csr([data.graph], train_nodes=[train],
                            fanouts=[fanout], t_pad_min=B)
    key = jax.random.PRNGKey(7)
    tables, masks, batches, bmasks = jax.tree_util.tree_map(
        np.asarray, sample_round_device(dcsr, key, K, fanout, B))
    assert tables.shape == (1, K, data.num_nodes, fanout)
    assert batches.shape == (1, K, B)
    deg = data.graph.degrees()
    for s in range(K):
        for v in range(data.num_nodes):
            nbrs = set(data.graph.neighbors(v).tolist())
            w = min(int(deg[v]), fanout)
            row, m = tables[0, s, v], masks[0, s, v]
            np.testing.assert_array_equal(m, (np.arange(fanout) < w))
            got = row[:w].tolist()
            assert set(got) <= nbrs                  # true neighbors
            assert len(set(got)) == w                # without replacement
            if deg[v] <= fanout:                     # keeps ALL neighbors
                assert set(got) == nbrs
            np.testing.assert_array_equal(row[w:], 0)  # masked slots zeroed
        b = batches[0, s]
        assert set(b.tolist()) <= set(train.tolist())
        if train.size >= B:
            assert len(set(b.tolist())) == B         # WOR batch
    np.testing.assert_array_equal(bmasks, 1.0)


def test_device_stream_replay_and_prefix_identity(tiny):
    data, _ = tiny
    fanout, B = 5, 8
    dcsr = build_device_csr([data.graph],
                            train_nodes=[data.train_nodes.astype(np.int64)],
                            fanouts=[fanout], t_pad_min=B)
    base = jax.random.PRNGKey(0)
    k1 = jax.random.fold_in(base, 1)
    a = sample_round_device(dcsr, k1, 4, fanout, B)
    b = sample_round_device(dcsr, k1, 4, fanout, B)
    _assert_trees_equal(a, b)                        # deterministic replay
    c = sample_round_device(dcsr, jax.random.fold_in(base, 2), 4, fanout, B)
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    # the K-bucketing anchor: a longer (padded) draw agrees on the prefix
    long = sample_round_device(dcsr, k1, 7, fanout, B)
    for x, y in zip(a, long):
        np.testing.assert_array_equal(np.asarray(x),
                                      np.asarray(y)[:, :4])


# --------------------------------------------------------------------------
# 2. plan-level differentials
# --------------------------------------------------------------------------
def test_device_matches_host_shapes_and_mask_invariants(tiny):
    data, model = tiny
    ph, pd = _plan("host"), _plan("device")
    descs = lower_plan(pd)
    sh, sd = RoundSampler(data, model, ph), RoundSampler(data, model, pd)
    ih, idv = sh.sample(descs[0]), sd.sample(descs[0])
    assert ih.tables.shape == idv.tables.shape
    assert ih.masks.shape == idv.masks.shape
    assert ih.batches.shape == idv.batches.shape
    assert ih.bmasks.shape == idv.bmasks.shape
    # same masked-slot discipline: entries beyond the mask are zero
    t, m = np.asarray(idv.tables), np.asarray(idv.masks)
    np.testing.assert_array_equal(t[m == 0.0], 0)
    # per-machine padded rows (beyond n_local) are fully masked
    for p in range(sd.num_machines):
        assert m[p, :, sd.n_local[p]:].sum() == 0.0


def test_overlap_is_bit_identical_to_synchronous(tiny):
    data, model = tiny
    h_ov = build_trainer(data, model, _plan("device", overlap=True)).run()
    h_sync = build_trainer(data, model, _plan("device", overlap=False)).run()
    assert h_ov.val_score == h_sync.val_score
    assert h_ov.train_loss == h_sync.train_loss
    assert h_ov.meta["local_loss"] == h_sync.meta["local_loss"]
    _assert_trees_equal(h_ov.meta["final_params"],
                        h_sync.meta["final_params"])
    assert h_ov.meta["sampler_overlap"] and not h_sync.meta["sampler_overlap"]


def test_host_placement_overlap_preserves_legacy_stream(tiny):
    """prefetch only moves WHERE the host draw happens, never its order."""
    data, model = tiny
    h_def = build_trainer(data, model, _plan("host")).run()
    h_ov = build_trainer(data, model, _plan("host", overlap=True)).run()
    assert h_def.val_score == h_ov.val_score
    assert h_def.meta["local_loss"] == h_ov.meta["local_loss"]
    _assert_trees_equal(h_def.meta["final_params"],
                        h_ov.meta["final_params"])


def test_device_adds_no_new_round_compiles_under_bucketing(tiny):
    data, model = tiny
    h_host = build_trainer(data, model,
                           _plan("host", bucketing=True, rounds=6)).run()
    h_dev = build_trainer(data, model,
                          _plan("device", bucketing=True, rounds=6)).run()
    # identical round-program compile count: the device tables feed the
    # SAME bucketed shapes the host padder produces
    assert h_dev.meta["num_retraces"] == h_host.meta["num_retraces"]
    # and the jitted sampler itself compiles once per bucket, not per round
    assert (h_dev.meta["sampler_retraces"]
            == len(h_dev.meta["bucket_lengths"])
            < len(h_dev.rounds))
    assert h_host.meta["sampler_retraces"] == 0
    # bucketed device run trains to the same trajectory as unbucketed
    h_flat = build_trainer(data, model, _plan("device", rounds=6)).run()
    assert h_flat.val_score == h_dev.val_score
    assert h_flat.train_loss == h_dev.train_loss
    _assert_trees_equal(h_flat.meta["final_params"],
                        h_dev.meta["final_params"])


def test_rng_compat_requires_host_placement():
    with pytest.raises(ValueError, match="rng_compat"):
        _plan("device", rng_compat=True)


def test_prewarm_caches_every_graph_fanout_plan(tiny):
    """Satellite: hybrid halo→LLCG plans must not re-pay sampling-plan
    construction at the switch round — prewarm builds all of them."""
    data, model = tiny
    hybrid = _plan(phases=(halo_exchange(first=2),
                           local_steps(after=2), averaging(after=2),
                           correction(after=2)))
    sampler = RoundSampler(data, model, hybrid)
    sampler.prewarm({d.kind for d in lower_plan(hybrid)})
    for ld in sampler.loaders:
        cache = ld.sampler.graph.__dict__.get("_sampling_plans")
        assert cache and ld.sampler.fanout in cache
    for g in sampler.halo_plan.ext_graphs:
        cache = g.__dict__.get("_sampling_plans")
        assert cache and sampler.fanout_ext in cache


def test_device_placement_runs_hybrid_halo_plan(tiny):
    """Ext (halo) rounds also sample on device and still train."""
    data, model = tiny
    hybrid = _plan("device",
                   phases=(halo_exchange(first=2),
                           local_steps(after=2), averaging(after=2),
                           correction(after=2)))
    hist = build_trainer(data, model, hybrid).run()
    assert len(hist.val_score) == 4
    assert all(np.isfinite(v) for v in hist.meta["local_loss"])
    assert hist.meta["sampler_placement"] == "device"
    assert hist.meta["sampler_select"] == {"ext": "row_table",
                                           "local": "row_table"}


def test_sampler_stats_report_the_select_per_kind(tiny):
    """Each kind's DeviceCSR reports how its ids are read; host placement
    builds none."""
    data, model = tiny
    kinds = {"local", "full"}
    dev = RoundSampler(data, model, _plan("device"))
    dev.prewarm(kinds)
    stats = dev.device_sampler_stats()
    assert stats["sampler_select"] == {"local": "row_table",
                                       "full": "row_table"}
    nbr = [dev._device_csr(k).nbr for k in sorted(kinds)]
    assert stats["sampler_row_table_bytes"] == sum(x.nbytes for x in nbr)
    assert nbr[0].shape == (1, data.num_nodes, data.graph.max_degree())
    host = RoundSampler(data, model, _plan("host"))
    host.prewarm(kinds)
    assert host.device_sampler_stats() == {"sampler_select": {},
                                           "sampler_row_table_bytes": 0}


# --------------------------------------------------------------------------
# 3. serving
# --------------------------------------------------------------------------
def test_serving_device_full_width_matches_host(tiny):
    data, model = tiny
    params = model.init(0)

    def serve(placement):
        eng = GNNServingEngine(model, params, data, num_machines=3,
                               partition_method="random", seed=0,
                               sampler_placement=placement)
        for uid in range(5):
            eng.submit(GNNRequest(uid=uid,
                                  nodes=[(uid * 31 + 7) % data.num_nodes]))
        res = eng.run()
        return [r.predictions for r in sorted(res, key=lambda r: r.uid)]

    # full width (the default) samples every neighbor — both placements
    # reproduce the exact full-neighbor forward
    assert serve("host") == serve("device")
    assert serve("device") == serve("device")        # replay determinism
    with pytest.raises(ValueError, match="sampler_placement"):
        GNNServingEngine(model, params, data, num_machines=2,
                         sampler_placement="gpu")


def test_serving_device_tables_full_width_are_exact(tiny):
    data, _ = tiny
    dcsr = build_device_csr([data.graph])
    width = max(data.graph.max_degree(), 1)
    tables, masks = jax.tree_util.tree_map(
        np.asarray,
        sample_serving_tables_device(dcsr, jax.random.PRNGKey(3), width))
    for v in range(data.num_nodes):
        w = int(masks[0, v].sum())
        assert set(tables[0, v, :w].tolist()) == \
            set(data.graph.neighbors(v).tolist())


# --------------------------------------------------------------------------
# 4. the row-table select against the CSR gather
# --------------------------------------------------------------------------
def _ref_rank_select(bits, valid, width):
    """The selection as drawn before the row table: slot indices."""
    dmax = bits.shape[-1]
    w = min(width, dmax)
    if dmax <= 128:
        ib = max(int(dmax - 1).bit_length(), 1)
        ia = jnp.arange(dmax, dtype=jnp.uint32)
        keys = jnp.where(
            valid,
            ((bits >> jnp.uint32(1 + ib)) << jnp.uint32(ib)) | ia,
            (jnp.uint32(1) << jnp.uint32(31)) | ia)
        gt = keys[..., :, None] > keys[..., None, :]
        rank = jnp.sum(gt, axis=-1, dtype=jnp.int32)
        slot = jnp.where(rank < w, rank, w)
        hit = slot[..., :, None] == jnp.arange(w, dtype=jnp.int32)
        sel = jnp.sum(jnp.where(hit, ia.astype(jnp.int32)[:, None], 0),
                      axis=-2)
    else:
        keys = jnp.where(valid, bits >> jnp.uint32(1),
                         jnp.uint32(0xFFFFFFFF))
        _, sel = jax.lax.top_k(keys ^ jnp.uint32(0xFFFFFFFF), w)
        sel = sel.astype(jnp.int32)
    if w < width:
        pad = jnp.zeros(sel.shape[:-1] + (width - w,), jnp.int32)
        sel = jnp.concatenate([sel, pad], axis=-1)
    return sel


def _ref_tables_step(step_key, indices_p, starts_p, degrees_p, fanout_p,
                     width, dmax):
    """One machine-step's table: the selected slots gathered out of the
    CSR indices, one scalar lookup per slot."""
    n_pad = starts_p.shape[0]
    e_pad = indices_p.shape[0]
    bits = jax.random.bits(jax.random.fold_in(step_key, 0), (n_pad, dmax),
                           dtype=jnp.uint32)
    col = jnp.arange(dmax, dtype=jnp.int32)
    sel = _ref_rank_select(bits, col[None, :] < degrees_p[:, None], width)
    eff = jnp.minimum(degrees_p, fanout_p)
    valid = jnp.arange(width, dtype=jnp.int32)[None, :] < eff[:, None]
    gat = jnp.clip(starts_p[:, None] + sel, 0, e_pad - 1)
    table = jnp.where(valid, indices_p[gat], 0).astype(jnp.int32)
    return table, valid.astype(jnp.float32)


def _ref_batch_step(step_key, train_p, count_p, batch_size):
    t_pad = train_p.shape[0]
    bits = jax.random.bits(jax.random.fold_in(step_key, 1), (t_pad,),
                           dtype=jnp.uint32)
    valid = jnp.arange(t_pad, dtype=jnp.int32) < count_p
    wor = _ref_rank_select(bits, valid, batch_size)
    rep = jax.random.randint(jax.random.fold_in(step_key, 2), (batch_size,),
                             0, jnp.maximum(count_p, 1))
    sel = jnp.where(count_p >= batch_size, wor[:batch_size], rep)
    return train_p[sel].astype(jnp.int32)


def _csr_stack(graphs, n_pad):
    e_pad = max(max(g.num_edges for g in graphs), 1)
    indices = np.zeros((len(graphs), e_pad), np.int32)
    starts = np.zeros((len(graphs), n_pad), np.int32)
    degrees = np.zeros((len(graphs), n_pad), np.int32)
    for p, g in enumerate(graphs):
        indices[p, :g.num_edges] = g.indices
        starts[p, :g.num_nodes] = g.indptr[:-1]
        degrees[p, :g.num_nodes] = g.degrees()
    return indices, starts, degrees


def _ref_round(graphs, n_pad, pools, fanouts, key, num_steps, width, B):
    indices, starts, degrees = _csr_stack(graphs, n_pad)
    t_pad = max(max(t.size for t in pools), B)
    tn = np.zeros((len(graphs), t_pad), np.int32)
    for p, t in enumerate(pools):
        tn[p, :t.size] = t
    tc = np.asarray([t.size for t in pools], np.int32)
    dmax = max(g.max_degree() for g in graphs)

    def one_machine(p, ix, st, dg, tr, ct, fo):
        kp = jax.random.fold_in(key, p)

        def one_step(s):
            ks = jax.random.fold_in(kp, s)
            table, mask = _ref_tables_step(ks, ix, st, dg, fo, width, dmax)
            return table, mask, _ref_batch_step(ks, tr, ct, B)

        return jax.vmap(one_step)(jnp.arange(num_steps))

    return jax.vmap(one_machine)(
        jnp.arange(len(graphs)), indices, starts, degrees, tn, tc,
        jnp.asarray(fanouts, jnp.int32))


def _ref_serving(graphs, n_pad, key, width):
    indices, starts, degrees = _csr_stack(graphs, n_pad)
    dmax = max(g.max_degree() for g in graphs)

    def one_machine(p, ix, st, dg):
        ks = jax.random.fold_in(jax.random.fold_in(key, p), 0)
        return _ref_tables_step(ks, ix, st, dg, jnp.int32(width), width,
                                dmax)

    return jax.vmap(one_machine)(jnp.arange(len(graphs)), indices, starts,
                                 degrees)


def _graph_with_degrees(deg, num_nodes, rng):
    """A CSR graph whose rows have the given degrees (distinct neighbors,
    unsorted so the slot order is not the id order)."""
    deg = np.asarray(deg, np.int64)
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    indices = np.concatenate(
        [rng.permutation(num_nodes)[:d] for d in deg]
        or [np.zeros(0, np.int64)]).astype(np.int32)
    return CSRGraph(indptr=indptr, indices=indices, num_nodes=len(deg))


def _shards(max_deg, rng):
    """Three shards of different sizes: degree-0 rows, rows below the
    fanout, rows at ``max_deg``, and padded rows past the smaller ones."""
    sizes = (40, 33, 25)
    graphs = []
    for n in sizes:
        deg = rng.integers(0, max_deg + 1, size=n)
        deg[:3] = (0, 1, max_deg)
        graphs.append(_graph_with_degrees(deg, 200, rng))
    return graphs


@pytest.mark.parametrize("max_deg,select", [(12, "row_table"),
                                            (130, "row_gather")])
def test_device_round_equals_the_csr_gather(max_deg, select):
    rng = np.random.default_rng(max_deg)
    graphs = _shards(max_deg, rng)
    n_pad, K, B, width = 41, 3, 6, 7
    fanouts = [width, 4, 1]                          # narrower per machine
    pools = [rng.choice(g.num_nodes, size=s, replace=False).astype(np.int32)
             for g, s in zip(graphs, (20, 4, 9))]    # one pool below B
    dcsr = build_device_csr(graphs, n_pad=n_pad, train_nodes=pools,
                            fanouts=fanouts, t_pad_min=B)
    assert dcsr.select == select
    assert dcsr.nbr.shape == (3, n_pad, max_deg)
    for seed in (0, 1, 2, 2**31 + 5):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 3)
        got = sample_round_device(dcsr, key, K, width, B)
        want = _ref_round(graphs, n_pad, pools, fanouts, key, K, width, B)
        _assert_trees_equal(got[:3], want)


@pytest.mark.parametrize("max_deg,select", [(12, "row_table"),
                                            (130, "row_gather")])
@pytest.mark.parametrize("width_of", ["full", "narrow", "wide"])
def test_device_serving_tables_equal_the_csr_gather(max_deg, select,
                                                    width_of):
    rng = np.random.default_rng(max_deg + 1)
    graphs = _shards(max_deg, rng)
    width = {"full": max_deg, "narrow": 5, "wide": max_deg + 3}[width_of]
    dcsr = build_device_csr(graphs, n_pad=41)
    assert dcsr.select == select
    for seed in (0, 7, 2**31 + 5):
        key = jax.random.PRNGKey(seed)
        _assert_trees_equal(
            sample_serving_tables_device(dcsr, key, width),
            _ref_serving(graphs, 41, key, width))
