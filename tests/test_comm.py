"""Compressed-communication layer: codecs, engine threading, accounting.

Five properties anchor the layer:

1. The Pallas quantize/dequantize kernels match the jnp oracles (scales to
   float tolerance — XLA fusion order costs 1 ulp on the scale, which may
   flip a floor boundary, so quantized values match within ±1 level).
2. Stochastic rounding is unbiased: averaging dequantized draws over many
   uniform samples recovers the input.
3. ``compression="none"`` is BIT-identical to the pre-compression plans
   (trajectory, bytes, final params) — the legacy strategy shims are the
   frozen pre-PR behavior the plan path must keep reproducing.
4. ``accounting()`` totals equal the executed ``History`` byte stream for
   every canned plan × codec — the accounting layer prices what actually
   moves.
5. Error feedback does its job: the int8_ef final iterate is closer to the
   uncompressed run's final iterate than plain int8's (the EF-SGD
   convergence argument, measured in parameter space), and the shard_map
   backend draws bit-identical stochastic rounding to vmap (subprocess,
   slow).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.compress import (
    COMPRESSIONS, HALO_COMPRESSIONS, averaging_payload_bytes,
    check_compression, compress_features, compress_tree,
    decompress_features, decompress_tree, wire_row_bytes,
)
from repro.core import DistConfig, build_trainer
from repro.core.plan import (
    CommSpec, ggs_plan, llcg_plan, psgd_pa_plan, single_machine_plan,
)
from repro.core.strategies import run_ggs, run_llcg, run_psgd_pa
from repro.graph import sbm_graph
from repro.kernels import ref
from repro.kernels.ops import dequantize_int8_rows, quantize_int8_rows
from repro.models.gnn import build_model
from repro.utils.pytree import tree_bytes

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="module")
def tiny():
    data = sbm_graph(num_nodes=160, num_classes=3, feature_dim=8,
                     feature_snr=0.4, homophily=0.9, avg_degree=8, seed=1)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=16)
    cfg = DistConfig(num_machines=2, rounds=3, local_k=3, batch_size=8,
                     server_batch_size=16, fanout=5, correction_steps=2,
                     partition_method="random", seed=3)
    return data, model, cfg


def _with_comm(plan, **kw):
    return dataclasses.replace(plan,
                               comm=dataclasses.replace(plan.comm, **kw))


# --------------------------------------------------------------------------
# 1. kernels vs oracles
# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(1, 7), (5, 33), (37, 128), (130, 65)])
def test_quantize_kernel_matches_oracle(shape):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape) * 3.0, jnp.float32)
    u = jnp.asarray(rng.random(shape), jnp.float32)
    qk, sk = quantize_int8_rows(x, u)
    qr, sr = ref.quantize_int8_rows_ref(x, u)
    # scale: same formula, XLA fusion order costs ≤ 1 ulp
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    # a 1-ulp scale flip can move floor() one level at a boundary
    assert int(np.abs(np.asarray(qk, np.int32)
                      - np.asarray(qr, np.int32)).max()) <= 1
    dk = dequantize_int8_rows(qk, sk)
    np.testing.assert_allclose(np.asarray(dk),
                               np.asarray(ref.dequantize_int8_rows_ref(
                                   qk, sk)), rtol=1e-6)
    # reconstruction error bounded by one quantization level per row
    err = np.abs(np.asarray(dk) - np.asarray(x))
    assert (err <= np.asarray(sk) * 1.001).all()


def test_quantize_deterministic_default_is_round_nearest():
    x = jnp.asarray([[0.4, -0.4, 126.6, -126.6]], jnp.float32)
    q, s = quantize_int8_rows(x)           # u=None -> round-half-up
    d = np.asarray(dequantize_int8_rows(q, s))
    np.testing.assert_allclose(d, np.asarray(x), atol=float(s[0, 0]) / 2
                               + 1e-6)


def test_stochastic_rounding_is_unbiased():
    x = jnp.asarray(np.linspace(-2.0, 2.0, 16)[None], jnp.float32)
    key = jax.random.PRNGKey(0)
    acc = np.zeros(x.shape, np.float64)
    n = 400
    for i in range(n):
        u = jax.random.uniform(jax.random.fold_in(key, i), x.shape)
        q, s = quantize_int8_rows(x, u)
        acc += np.asarray(dequantize_int8_rows(q, s), np.float64)
    scale = 2.0 / 127.0                    # one quantization level
    np.testing.assert_allclose(acc / n, np.asarray(x),
                               atol=3 * scale / np.sqrt(n))


# --------------------------------------------------------------------------
# codec roundtrips + wire pricing
# --------------------------------------------------------------------------
def test_compress_tree_roundtrip_and_pricing():
    rng = np.random.default_rng(1)
    tree = {"w": jnp.asarray(rng.standard_normal((4, 6)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((5,)), jnp.float32)}
    assert averaging_payload_bytes(tree, "none") == tree_bytes(tree)
    assert averaging_payload_bytes(tree, "bf16") == 2 * (24 + 5)
    assert averaging_payload_bytes(tree, "int8") == (24 + 4) + (5 + 4)
    for comp in COMPRESSIONS:
        payload, scales = compress_tree(tree, comp)
        out = decompress_tree(payload, scales, comp)
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(tree)):
            assert a.shape == b.shape and a.dtype == jnp.float32
            tol = 0.0 if comp == "none" else 0.05
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=tol)
    # per machine under vmap, keys folded from axis_index as the engine
    # does: per-machine payloads and scales
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([x, 2 * x]), tree)

    def one_machine(delta):
        key = jax.random.fold_in(jax.random.PRNGKey(0),
                                 jax.lax.axis_index("machine"))
        return compress_tree(delta, "int8", key=key)

    payload, scales = jax.vmap(one_machine, axis_name="machine")(stacked)
    assert all(s.shape[0] == 2 for s in jax.tree_util.tree_leaves(scales))
    out = decompress_tree(payload, scales, "int8")
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(stacked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.1)


def test_compress_features_roundtrip_and_row_pricing():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((9, 16)), jnp.float32)
    assert wire_row_bytes(16) == 64.0
    assert wire_row_bytes(16, compression="bf16") == 32.0
    assert wire_row_bytes(16, compression="int8") == 20.0
    for comp in HALO_COMPRESSIONS:
        payload, scales = compress_features(x, comp)
        out = decompress_features(payload, scales, comp)
        tol = 0.0 if comp == "none" else 0.05
        np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                                   atol=tol)


def test_compression_validation():
    for name in COMPRESSIONS:
        check_compression(name)
    with pytest.raises(ValueError, match="compression"):
        check_compression("int4")
    with pytest.raises(ValueError, match="halo_compression"):
        check_compression("int8_ef", halo=True)   # EF needs carried state
    with pytest.raises(ValueError, match="compression"):
        CommSpec(num_machines=2, compression="fp8")
    with pytest.raises(ValueError, match="halo_compression"):
        CommSpec(num_machines=2, halo_compression="int8_ef")
    with pytest.raises(ValueError, match="host_halo"):
        CommSpec(num_machines=2, host_halo=True, halo_compression="int8")


# --------------------------------------------------------------------------
# 3. compression="none" is bit-identical to the pre-compression plans
# --------------------------------------------------------------------------
def _assert_history_equal(got, want):
    assert got.val_score == want.val_score
    assert got.train_loss == want.train_loss
    assert got.bytes_cum == want.bytes_cum
    assert got.steps_cum == want.steps_cum
    for a, b in zip(jax.tree_util.tree_leaves(got.meta["final_params"]),
                    jax.tree_util.tree_leaves(want.meta["final_params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_none_bit_identical_to_legacy(tiny):
    """Explicit compression='none' reproduces the frozen legacy shims
    bit-for-bit — the no-compression path kept its exact expressions."""
    data, model, cfg = tiny
    for plan_fn, legacy in ((psgd_pa_plan, run_psgd_pa),
                            (llcg_plan, run_llcg),
                            (ggs_plan, run_ggs)):
        plan = _with_comm(plan_fn(cfg), compression="none",
                          halo_compression="none")
        _assert_history_equal(build_trainer(data, model, plan).run(),
                              legacy(data, model, cfg))


# --------------------------------------------------------------------------
# 4. accounting == executed bytes, every canned plan × codec
# --------------------------------------------------------------------------
@pytest.mark.parametrize("plan_fn,field,codecs", [
    (psgd_pa_plan, "compression", COMPRESSIONS),
    (llcg_plan, "compression", ("none", "int8_ef")),
    (ggs_plan, "halo_compression", HALO_COMPRESSIONS),
    (single_machine_plan, "compression", ("none", "int8")),
])
def test_accounting_matches_history(tiny, plan_fn, field, codecs):
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, rounds=2, local_k=2)
    for codec in codecs:
        plan = _with_comm(plan_fn(cfg), **{field: codec})
        trainer = build_trainer(data, model, plan)
        acct = trainer.accounting()
        hist = trainer.run()
        np.testing.assert_allclose(
            hist.bytes_cum,
            np.cumsum([r["bytes"] for r in acct]),
            err_msg=f"{plan.name} × {field}={codec}")
        assert np.isfinite(hist.train_loss).all()


# --------------------------------------------------------------------------
# 5. error feedback + engine state threading
# --------------------------------------------------------------------------
def test_int8_ef_tracks_uncompressed_closer(tiny):
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, num_machines=4, rounds=8,
                              optimizer="sgd", lr=0.05)
    base = psgd_pa_plan(cfg)
    final = {}
    for comp in ("none", "int8", "int8_ef"):
        hist = build_trainer(data, model,
                             _with_comm(base, compression=comp)).run()
        final[comp] = hist.meta["final_params"]

    def dist(a, b):
        return float(jnp.sqrt(sum(
            jnp.sum((x - y) ** 2)
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b)))))

    d8, def_ = dist(final["int8"], final["none"]), \
        dist(final["int8_ef"], final["none"])
    assert d8 > 0 and def_ > 0          # compression really perturbed
    assert def_ < d8, (
        f"error feedback must land closer to the uncompressed iterate: "
        f"int8_ef {def_:.2e} vs int8 {d8:.2e}")


def test_ef_residual_state_threading(tiny):
    """int8_ef carries a per-machine residual in EngineState; other codecs
    carry none."""
    from repro.core import EngineConfig, RoundProgram
    data, model, cfg = tiny
    from repro.core.strategies import _Context
    from repro.core import RoundInputs
    from repro.data.graph_loader import sample_round
    ctx = _Context(data, model, cfg)
    params0 = model.init(cfg.seed)
    arrs = sample_round(ctx.loaders, cfg.local_k, cfg.batch_size,
                        ctx.n_max, ctx.fanout, ctx.rng)
    inputs = RoundInputs(*(jnp.asarray(a) for a in arrs))
    for comp, has_res in (("none", False), ("bf16", False),
                          ("int8", False), ("int8_ef", True)):
        prog = RoundProgram(
            model, ctx.opt, None,
            EngineConfig(num_machines=cfg.num_machines, mode="local",
                         backend="vmap", with_correction=False,
                         compression=comp))
        state = prog.init_state(params0)
        assert (state.comm_residual is not None) == has_res
        state, _ = prog.run_round(state, ctx.feats_j, ctx.labels_j, inputs)
        if has_res:
            res_norm = sum(float(jnp.abs(l).sum()) for l in
                           jax.tree_util.tree_leaves(state.comm_residual))
            assert res_norm > 0         # quantization error was captured
            leaves = jax.tree_util.tree_leaves(state.comm_residual)
            assert all(l.shape[0] == cfg.num_machines for l in leaves)
        else:
            assert state.comm_residual is None


def test_compressed_rounds_are_deterministic(tiny):
    """Same plan, same seed ⇒ same stochastic draws ⇒ same trajectory."""
    data, model, cfg = tiny
    plan = _with_comm(psgd_pa_plan(cfg), compression="int8_ef")
    h1 = build_trainer(data, model, plan).run()
    h2 = build_trainer(data, model, plan).run()
    assert h1.train_loss == h2.train_loss
    assert h1.bytes_cum == h2.bytes_cum


# --------------------------------------------------------------------------
# halo compression: engine + serving
# --------------------------------------------------------------------------
def test_halo_compressed_round_close_to_uncompressed(tiny):
    data, model, cfg = tiny
    base = ggs_plan(cfg)
    h0 = build_trainer(data, model, base).run()
    h8 = build_trainer(data, model,
                       _with_comm(base, halo_compression="int8")).run()
    assert h8.bytes_cum[-1] < h0.bytes_cum[-1]
    assert (h8.meta["exchange_bytes_per_step"]
            < h0.meta["exchange_bytes_per_step"])
    # int8 feature rows perturb the forward only slightly
    np.testing.assert_allclose(h8.train_loss, h0.train_loss, atol=0.05)


def test_serving_halo_compression(tiny):
    from repro.serving import GNNRequest, GNNServingEngine
    data, model, _ = tiny
    params = model.init(0)
    engines = {
        comp: GNNServingEngine(model, params, data, num_machines=3,
                               seed=2, halo_compression=comp)
        for comp in ("none", "int8")}
    results = {}
    for comp, eng in engines.items():
        for uid in range(4):
            eng.submit(GNNRequest(uid=uid, nodes=[uid * 11 % 160,
                                                  (uid * 7 + 3) % 160]))
        results[comp] = eng.run()
    s0 = engines["none"].backend.stats()
    s8 = engines["int8"].backend.stats()
    assert s8["exchange_bytes_per_wave"] < s0["exchange_bytes_per_wave"]
    assert s8["halo_compression"] == "int8"
    for a, b in zip(results["none"], results["int8"]):
        assert a.nodes == b.nodes and len(a.predictions) == 2
    with pytest.raises(ValueError, match="halo_compression"):
        GNNServingEngine(model, params, data, num_machines=3,
                         halo_compression="int8_ef")


# --------------------------------------------------------------------------
# backend agreement under compression (subprocess: forced 2-device host)
# --------------------------------------------------------------------------
@pytest.mark.slow
def test_backends_agree_compressed():
    """vmap and shard_map must draw IDENTICAL stochastic-rounding bits
    (both fold the machine's axis_index into the key) — params agree
    bit-exactly for every codec, and so does int8_ef's residual."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from repro.core import DistConfig, EngineConfig, RoundInputs, RoundProgram
from repro.core.strategies import _Context
from repro.data.graph_loader import sample_round
from repro.graph import sbm_graph
from repro.models.gnn import build_model

data = sbm_graph(num_nodes=120, num_classes=3, feature_dim=8,
                 feature_snr=0.4, homophily=0.9, seed=0)
model = build_model("GG", data.feature_dim, data.num_classes, hidden_dim=16)
cfg = DistConfig(num_machines=2, rounds=2, local_k=3, batch_size=8,
                 server_batch_size=16, fanout=5,
                 partition_method="random", seed=0)
mesh = Mesh(np.asarray(jax.devices()[:2]), ("machine",))
out = {}
for comp in ("bf16", "int8", "int8_ef"):
    ctx = _Context(data, model, cfg)
    progs = {
        "vmap": RoundProgram(model, ctx.opt, None,
            EngineConfig(num_machines=2, mode="local", backend="vmap",
                         with_correction=False, compression=comp)),
        "shard_map": RoundProgram(model, ctx.opt, None,
            EngineConfig(num_machines=2, mode="local", backend="shard_map",
                         with_correction=False, compression=comp),
            mesh=mesh),
    }
    params0 = model.init(cfg.seed)
    states = {k: p.init_state(params0) for k, p in progs.items()}
    max_diff = 0.0
    with mesh:
        for r in range(cfg.rounds):
            arrs = sample_round(ctx.loaders, cfg.local_k, cfg.batch_size,
                                ctx.n_max, ctx.fanout, ctx.rng)
            inputs = RoundInputs(*(jnp.asarray(a) for a in arrs))
            for k in progs:
                states[k], _ = progs[k].run_round(states[k], ctx.feats_j,
                                                  ctx.labels_j, inputs)
            pair = [(s.params, s.comm_residual) for s in states.values()]
            for a, b in zip(*(jax.tree_util.tree_leaves(t) for t in pair)):
                max_diff = max(max_diff, float(jnp.abs(a - b).max()))
    out[comp] = max_diff
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for comp, diff in out.items():
        assert diff == 0.0, (
            f"{comp}: backends disagree by {diff} — the compressed "
            "collective must be bit-identical across backends")
