"""Unified round engine — equivalence vs the pre-refactor sequential loop.

1. One engine round (vmap backend) must match a hand-rolled per-machine
   Python step loop on IDENTICAL round inputs, tightly.
2. A full `run_llcg` trajectory must match the sequential reference driven
   by the same RNG streams, loosely (fp reassociation across vmap/mean).
3. vmap and shard_map backends must agree on the same round inputs
   (subprocess — needs a multi-device host, marked slow).
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

from repro.core import (
    DistConfig, EngineConfig, RoundInputs, RoundProgram, run_llcg,
)
from repro.core.machine import make_machine_step
from repro.core.strategies import _Context
from repro.data.graph_loader import sample_round
from repro.graph import sbm_graph
from repro.graph.csr import build_neighbor_table
from repro.models.gnn import build_model
from repro.utils.pytree import tree_average

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


def test_vmap_round_matches_sequential_steps(tiny):
    """One engine round == P×K individual jit'd steps, on the same inputs."""
    data, model, cfg = tiny
    ctx = _Context(data, model, cfg)
    inputs_np = sample_round(ctx.loaders, cfg.local_k, cfg.batch_size,
                             ctx.n_max, ctx.fanout, ctx.rng)
    inputs = RoundInputs(*(jnp.asarray(a) for a in inputs_np),
                         **ctx.sample_correction())
    program = RoundProgram(
        model, ctx.opt, ctx.server_opt,
        EngineConfig(num_machines=cfg.num_machines, mode="local",
                     backend="vmap", with_correction=True))
    params0 = model.init(cfg.seed)
    state = program.init_state(params0)
    state, _ = program.run_round(state, ctx.feats_j, ctx.labels_j, inputs)

    # sequential reference: the pre-engine per-step loop, its correction on
    # the single full-neighbor table (the engine runs the degree buckets)
    table, mask = map(jnp.asarray, build_neighbor_table(data.graph))
    sstep = make_machine_step(model, ctx.server_opt)
    P = cfg.num_machines
    local = []
    for p in range(P):
        params_p, opt_p = params0, ctx.opt.init(params0)
        for k in range(cfg.local_k):
            params_p, opt_p, _ = ctx.step.local_step(
                params_p, opt_p, ctx.feats_j[p], inputs.tables[p, k],
                inputs.masks[p, k], inputs.batches[p, k], ctx.labels_j[p],
                inputs.bmasks[p, k])
        local.append(params_p)
    ref = tree_average(local)
    so = ctx.server_opt.init(params0)
    for s in range(cfg.correction_steps):
        ref, so, _ = sstep.local_step(
            ref, so, inputs.corr_feats, table, mask,
            inputs.corr_batches[s], inputs.corr_labels,
            inputs.corr_bmasks[s])

    for got, want in zip(jax.tree_util.tree_leaves(state.params),
                         jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_run_llcg_trajectory_matches_sequential_reference(tiny):
    """Full run: same RNG streams ⇒ same val/loss trajectory (loose tol)."""
    data, model, cfg = tiny
    engine_hist = run_llcg(data, model, cfg)

    # reference run re-creates the context (identical seeds → identical
    # sampler/batch RNG streams) and loops machines/steps in Python
    ctx = _Context(data, model, cfg)
    table, mask = map(jnp.asarray, build_neighbor_table(data.graph))
    sstep = make_machine_step(model, ctx.server_opt)
    params = model.init(cfg.seed)
    server_state = ctx.server_opt.init(params)
    ref_scores, ref_losses = [], []
    for _ in range(cfg.rounds):
        tables, masks, batches, bmasks = sample_round(
            ctx.loaders, cfg.local_k, cfg.batch_size, ctx.n_max, ctx.fanout,
            ctx.rng)
        corr = ctx.sample_correction()
        local = []
        for p in range(cfg.num_machines):
            params_p, opt_p = params, ctx.opt.init(params)
            for k in range(cfg.local_k):
                params_p, opt_p, _ = ctx.step.local_step(
                    params_p, opt_p, ctx.feats_j[p],
                    jnp.asarray(tables[p, k]), jnp.asarray(masks[p, k]),
                    jnp.asarray(batches[p, k]), ctx.labels_j[p],
                    jnp.asarray(bmasks[p, k]))
            local.append(params_p)
        params = tree_average(local)
        for s in range(cfg.correction_steps):
            params, server_state, _ = sstep.local_step(
                params, server_state, corr["corr_feats"], table, mask,
                corr["corr_batches"][s], corr["corr_labels"],
                corr["corr_bmasks"][s])
        loss, score = ctx.evaluate(params, data.val_nodes)
        ref_losses.append(loss)
        ref_scores.append(score)

    np.testing.assert_allclose(engine_hist.train_loss, ref_losses, atol=1e-2)
    np.testing.assert_allclose(engine_hist.val_score, ref_scores, atol=0.05)


def test_llcg_byte_accounting_is_per_round(tiny):
    data, model, cfg = tiny
    hist = run_llcg(data, model, cfg)
    pb = hist.meta["param_bytes"]
    expect = [2 * cfg.num_machines * pb * r for r in hist.rounds]
    np.testing.assert_allclose(hist.bytes_cum, expect)
    assert hist.steps_cum[-1] == cfg.num_machines * cfg.local_k * cfg.rounds


@pytest.mark.slow
@pytest.mark.parametrize("mode,reset,corr", [
    ("local", True, True),     # LLCG: fresh local optimizer each round
    ("local", False, True),    # persistent, stacked (P, …) local optimizer
    ("sync", True, False),     # per-step gradient pmean, shared optimizer
], ids=["local", "local-persistent-opt", "sync"])
def test_vmap_and_shard_map_backends_agree(mode, reset, corr):
    """Both backends, same round inputs ⇒ same params and optimizer state
    (subprocess: needs a forced multi-device host before jax init)."""
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

MODE, RESET, CORR = %r, %r, %r
data = sbm_graph(num_nodes=120, num_classes=3, feature_dim=8,
                 feature_snr=0.4, homophily=0.9, seed=0)
model = build_model("GG", data.feature_dim, data.num_classes, hidden_dim=16)
cfg = DistConfig(num_machines=2, rounds=2, local_k=3, batch_size=8,
                 server_batch_size=16, fanout=5, correction_steps=1,
                 partition_method="random", seed=0)
ctx = _Context(data, model, cfg)
mesh = Mesh(np.asarray(jax.devices()[:2]), ("machine",))
ecfg = dict(num_machines=2, mode=MODE, with_correction=CORR,
            reset_local_opt=RESET)
server = ctx.server_opt if CORR else None
progs = {
    "vmap": RoundProgram(model, ctx.opt, server,
        EngineConfig(backend="vmap", **ecfg)),
    "shard_map": RoundProgram(model, ctx.opt, server,
        EngineConfig(backend="shard_map", **ecfg), mesh=mesh),
}
params0 = model.init(cfg.seed)
states = {k: p.init_state(params0) for k, p in progs.items()}
max_diff = 0.0
with mesh:
    for r in range(cfg.rounds):
        arrs = sample_round(ctx.loaders, cfg.local_k, cfg.batch_size,
                            ctx.n_max, ctx.fanout, ctx.rng)
        inputs = RoundInputs(*(jnp.asarray(a) for a in arrs),
                             **(ctx.sample_correction() if CORR else {}))
        for k in progs:
            states[k], _ = progs[k].run_round(states[k], ctx.feats_j,
                                              ctx.labels_j, inputs)
        pair = [(s.params, s.local_opt_state) for s in states.values()]
        for a, b in zip(*(jax.tree_util.tree_leaves(t) for t in pair)):
            assert a.shape == b.shape, (a.shape, b.shape)
            max_diff = max(max_diff, float(jnp.abs(a - b).max()))
opt = jax.tree_util.tree_leaves(states["shard_map"].local_opt_state)
print(json.dumps({"max_diff": max_diff,
                  "opt_shapes": [list(x.shape) for x in opt]}))
""" % (mode, reset, corr)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["max_diff"] < 1e-4, out
    if mode == "local" and not reset:
        # the persistent local optimizer stays stacked per machine
        assert all(s[0] == 2 for s in out["opt_shapes"]), out
