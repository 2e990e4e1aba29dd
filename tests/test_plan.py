"""TrainPlan API — plan↔legacy equivalence + the new compositions.

1. Differential: hand-composed plans (NOT the canned constructors) through
   ``build_trainer`` must reproduce ``run_psgd_pa/run_llcg/run_ggs/
   run_single_machine`` Histories bit-identically on the vmap backend —
   trajectories, byte/step accounting AND final params.
2. The three previously-inexpressible scenarios run end-to-end and their
   byte/step accounting matches the closed-form expectation computed from
   the lowered round kinds (property-style, checked across configs
   WITHOUT training via ``PlanTrainer.accounting``).
3. Composition errors (no compute phase, halo+local in one round, missing
   averaging on P>1, bad spec values) raise at plan/lowering time with the
   allowed values — not deep inside a run.
4. train→checkpoint→serve: a plan's ``checkpoint_dir`` export restores
   into ``GNNServingEngine.from_plan`` with the plan's own topology.
5. shard_map: the same plans (including a hybrid) lower onto the
   device-per-machine backend and agree with vmap (subprocess, slow).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (
    CommSpec, CompileSpec, DistConfig, LocalSpec, RoundPhase, SamplerSpec,
    ScheduleSpec, ServerSpec, TrainPlan, averaging, build_trainer,
    correction, ggs_plan, halo_exchange, llcg_plan, local_steps, lower_plan,
    run_ggs, run_llcg, run_psgd_pa, run_single_machine, single_machine_plan,
)
from repro.graph import sbm_graph
from repro.models.gnn import build_model

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


def _hand_plan(cfg, phases, name, **overrides):
    """Compose a plan explicitly from the grouped specs (no canned helper),
    so the differential tests exercise the lowering, not a shared shim."""
    specs = dict(
        local=LocalSpec(local_k=cfg.local_k, batch_size=cfg.batch_size,
                        lr=cfg.lr, optimizer=cfg.optimizer),
        server=ServerSpec(correction_steps=cfg.correction_steps,
                          server_batch_size=cfg.server_batch_size,
                          server_lr=cfg.server_lr,
                          correction_sampling=cfg.correction_sampling,
                          max_cut_minibatch=cfg.max_cut_minibatch),
        comm=CommSpec(num_machines=cfg.num_machines,
                      partition_method=cfg.partition_method,
                      host_halo=cfg.ggs_host_halo),
        sampler=SamplerSpec(fanout=cfg.fanout),
        schedule=ScheduleSpec(rounds=cfg.rounds, rho=cfg.rho),
        compile=CompileSpec(rng_compat=cfg.rng_compat,
                            k_bucketing=cfg.k_bucketing,
                            bucket_mode=cfg.bucket_mode),
    )
    specs.update(overrides)
    return TrainPlan(phases=phases, name=name, seed=cfg.seed,
                     checkpoint_dir=cfg.checkpoint_dir, **specs)


def _assert_history_equal(got, want):
    assert got.val_score == want.val_score
    assert got.train_loss == want.train_loss
    assert got.bytes_cum == want.bytes_cum
    assert got.steps_cum == want.steps_cum
    for a, b in zip(jax.tree_util.tree_leaves(got.meta["final_params"]),
                    jax.tree_util.tree_leaves(want.meta["final_params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# 1. plan ↔ legacy bit-identity (vmap backend)
# --------------------------------------------------------------------------
def test_plan_reproduces_psgd_pa(tiny):
    data, model, cfg = tiny
    plan = _hand_plan(cfg, (local_steps(), averaging()), "psgd_pa",
                      schedule=ScheduleSpec(rounds=cfg.rounds, rho=1.0))
    _assert_history_equal(build_trainer(data, model, plan).run(),
                          run_psgd_pa(data, model, cfg))


def test_plan_reproduces_llcg(tiny):
    data, model, cfg = tiny
    plan = _hand_plan(cfg, (local_steps(), averaging(), correction()),
                      "llcg")
    _assert_history_equal(build_trainer(data, model, plan).run(),
                          run_llcg(data, model, cfg))


def test_plan_reproduces_llcg_rho_bucketed(tiny):
    """The ρ>1 schedule + fitted K-bucketing path, through the plan."""
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, rho=1.4, rounds=4, k_bucketing=True,
                              bucket_mode="fit")
    plan = _hand_plan(cfg, (local_steps(), averaging(), correction()),
                      "llcg")
    got = build_trainer(data, model, plan).run()
    want = run_llcg(data, model, cfg)
    _assert_history_equal(got, want)
    assert got.meta["num_retraces"] == want.meta["num_retraces"]
    assert got.meta["masked_steps"] == want.meta["masked_steps"]


def test_plan_reproduces_llcg_rng_compat_correction_sampling(tiny):
    """The legacy-RNG replay + sampling-at-correction ablation branch of
    RoundSampler.sample_correction, through the plan."""
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, rng_compat=True, correction_sampling=True)
    plan = _hand_plan(cfg, (local_steps(), averaging(), correction()),
                      "llcg")
    _assert_history_equal(build_trainer(data, model, plan).run(),
                          run_llcg(data, model, cfg))


@pytest.mark.parametrize("host_halo", [False, True])
def test_plan_reproduces_ggs(tiny, host_halo):
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, rounds=2, ggs_host_halo=host_halo)
    plan = _hand_plan(cfg, (halo_exchange(),), "ggs",
                      schedule=ScheduleSpec(rounds=cfg.rounds, rho=1.0))
    _assert_history_equal(build_trainer(data, model, plan).run(),
                          run_ggs(data, model, cfg))


def test_plan_reproduces_single_machine(tiny):
    data, model, cfg = tiny
    plan = _hand_plan(cfg, (local_steps(reset_opt=False),), "single",
                      comm=CommSpec(num_machines=1,
                                    partition_method="random"),
                      sampler=SamplerSpec(fanout=cfg.fanout,
                                          full_graph=True),
                      schedule=ScheduleSpec(rounds=cfg.rounds, rho=1.0))
    _assert_history_equal(build_trainer(data, model, plan).run(),
                          run_single_machine(data, model, cfg))


def test_p1_periodic_bytes_match_legacy_formula(tiny):
    """P=1 periodic strategies still charge 2·P·param_bytes per averaging
    round (the legacy accounting, averaging phase present) — only the
    single-machine plan, which has no averaging phase, charges 0."""
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, num_machines=1, rounds=2)
    h = run_llcg(data, model, cfg)
    pb = h.meta["param_bytes"]
    assert h.bytes_cum == [2 * pb, 4 * pb]
    assert run_single_machine(data, model, cfg).bytes_cum == [0.0, 0.0]


def test_uniform_history_meta(tiny):
    """num_retraces / masked_steps / cut_stats / local_loss are present on
    EVERY plan's History — including GGS, which used to lack cut_stats."""
    data, model, cfg = tiny
    small = dataclasses.replace(cfg, rounds=2)
    for fn in (run_psgd_pa, run_llcg, run_ggs, run_single_machine):
        h = fn(data, model, small)
        assert h.meta["num_retraces"] >= 1
        assert h.meta["masked_steps"] == 0
        assert "cut_fraction" in h.meta["cut_stats"]
        assert len(h.meta["local_loss"]) == small.rounds


# --------------------------------------------------------------------------
# 2. the new compositions + their accounting
# --------------------------------------------------------------------------
def test_correction_every_m(tiny):
    """correction(every=m): server steps only on every m-th round; m=1 is
    exactly LLCG."""
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, rounds=4)
    _assert_history_equal(
        build_trainer(data, model, llcg_plan(cfg, correction_every=1)).run(),
        run_llcg(data, model, cfg))
    h2 = build_trainer(data, model,
                       llcg_plan(cfg, correction_every=2)).run()
    assert h2.meta["corr_rounds"] == [2, 4]
    assert len(h2.meta["corr_loss"]) == 2
    # correction is server-side: byte accounting equals PSGD-PA/LLCG
    want = run_llcg(data, model, cfg)
    assert h2.bytes_cum == want.bytes_cum
    assert h2.steps_cum == want.steps_cum


def test_hybrid_halo_then_local(tiny):
    """halo_exchange for the first R0 rounds, then cheap LLCG rounds: the
    prefix is bit-identical to pure GGS, the accounting switches modes."""
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, rounds=4)
    r0 = 2
    plan = _hand_plan(cfg, (halo_exchange(first=r0),
                            local_steps(after=r0), averaging(after=r0),
                            correction(after=r0)), "hybrid",
                      schedule=ScheduleSpec(rounds=cfg.rounds, rho=1.0))
    trainer = build_trainer(data, model, plan)
    assert [d.kind for d in trainer.descs] == ["ext", "ext", "local",
                                               "local"]
    hist = trainer.run()
    ggs = run_ggs(data, model, dataclasses.replace(cfg, rounds=r0))
    assert hist.val_score[:r0] == ggs.val_score
    assert hist.train_loss[:r0] == ggs.train_loss
    assert hist.bytes_cum[:r0] == ggs.bytes_cum
    assert hist.meta["corr_rounds"] == [3, 4]
    # after the switch each round costs one parameter sync, nothing more
    P, pb = cfg.num_machines, hist.meta["param_bytes"]
    assert hist.bytes_cum[2] == ggs.bytes_cum[-1] + 2 * P * pb
    assert hist.bytes_cum[3] == ggs.bytes_cum[-1] + 4 * P * pb


def test_schedule_driven_switch(tiny):
    """Per-round strategy switching driven by the schedule: exact halo
    rounds while K is small, local rounds once the ρ-schedule grows K."""
    data, model, cfg = tiny
    thresh = 6
    big = lambda r, k: k >= thresh
    plan = _hand_plan(cfg, (halo_exchange(when=lambda r, k: k < thresh),
                            local_steps(when=big), averaging(when=big),
                            correction(when=big)), "switch",
                      schedule=ScheduleSpec(rounds=4, rho=1.6))
    trainer = build_trainer(data, model, plan)
    ks = trainer.schedule
    assert [d.kind for d in trainer.descs] == \
        ["ext" if k < thresh else "local" for k in ks]
    hist = trainer.run()
    assert len(hist.val_score) == 4
    assert all(np.isfinite(hist.train_loss))
    assert hist.meta["round_kinds"] == [d.kind for d in trainer.descs]


@pytest.mark.parametrize("m,r0,rounds", [(2, 1, 4), (3, 2, 5)])
def test_accounting_matches_closed_form(tiny, m, r0, rounds):
    """Property: lowered byte/step accounting equals the closed form for
    hybrid plans with correction-every-m — WITHOUT running any training."""
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, rounds=rounds)
    plan = _hand_plan(cfg, (halo_exchange(first=r0),
                            local_steps(after=r0), averaging(after=r0),
                            correction(after=r0, every=m)), "hybrid",
                      schedule=ScheduleSpec(rounds=rounds, rho=1.0))
    trainer = build_trainer(data, model, plan)
    acct = trainer.accounting()
    from repro.core import RoundSampler
    sampler = RoundSampler(data, model, plan)
    sampler.ensure_halo()
    P, pb = cfg.num_machines, sampler.param_bytes
    k = cfg.local_k
    for row in acct:
        if row["round"] <= r0:
            assert row["kind"] == "ext"
            expect = k * (sampler.exchange_bytes_per_step + 2 * P * pb)
        else:
            assert row["kind"] == "local"
            expect = 2 * P * pb
        assert row["bytes"] == expect
        assert row["steps"] == P * k
        assert row["correction"] == (row["round"] > r0
                                     and row["round"] % m == 0)


# --------------------------------------------------------------------------
# 3. construction-time validation
# --------------------------------------------------------------------------
def test_distconfig_validates_at_construction():
    with pytest.raises(ValueError, match="optimizer.*adam"):
        DistConfig(optimizer="rmsprop")
    with pytest.raises(ValueError, match="bucket_mode.*geometric"):
        DistConfig(bucket_mode="exact")
    with pytest.raises(ValueError, match="partition_method.*bfs"):
        DistConfig(partition_method="metis")
    with pytest.raises(ValueError, match="ρ"):
        DistConfig(rho=0.5)
    with pytest.raises(ValueError, match="fanout"):
        DistConfig(fanout=0)


def test_sharded_config_validates_at_construction():
    from repro.distributed.gnn_sharded import ShardedGNNConfig
    with pytest.raises(ValueError, match="mode.*llcg"):
        ShardedGNNConfig(mode="psgd")
    with pytest.raises(ValueError, match="partition_method"):
        ShardedGNNConfig(partition_method="metis")
    assert ShardedGNNConfig().to_plan().name == "llcg"


def test_plan_composition_errors(tiny):
    data, model, cfg = tiny
    with pytest.raises(ValueError, match="at least one phase"):
        TrainPlan(phases=())
    with pytest.raises(ValueError, match="no compute phase"):
        lower_plan(_hand_plan(cfg, (averaging(), correction()), "bad"))
    with pytest.raises(ValueError, match="cannot both"):
        lower_plan(_hand_plan(cfg, (local_steps(), averaging(),
                                    halo_exchange()), "bad"))
    with pytest.raises(ValueError, match="averages gradients every step"):
        lower_plan(_hand_plan(cfg, (halo_exchange(), averaging()), "bad"))
    with pytest.raises(ValueError, match="requires the averaging phase"):
        lower_plan(_hand_plan(cfg, (local_steps(),), "bad"))
    with pytest.raises(ValueError, match="full_graph.*num_machines=1"):
        _hand_plan(cfg, (local_steps(), averaging()), "bad",
                   sampler=SamplerSpec(fanout=5, full_graph=True))
    with pytest.raises(ValueError, match="phase kind"):
        RoundPhase("warmup")
    with pytest.raises(ValueError, match="backend"):
        build_trainer(data, model,
                      _hand_plan(cfg, (local_steps(), averaging()), "p"),
                      backend="pmap")


# --------------------------------------------------------------------------
# 4. train → checkpoint → serve through the plan object
# --------------------------------------------------------------------------
def test_plan_checkpoint_serve_roundtrip(tiny, tmp_path):
    """A NEW composition (correction-every-2) trains, exports per-round
    params through plan.checkpoint_dir, and GNNServingEngine.from_plan
    restores them with the plan's own partition topology."""
    from repro.serving import GNNRequest, GNNServingEngine
    data, model, cfg = tiny
    cfg = dataclasses.replace(cfg, rounds=2,
                              checkpoint_dir=str(tmp_path / "ckpt"))
    plan = llcg_plan(cfg, correction_every=2)
    hist = build_trainer(data, model, plan).run()
    engine = GNNServingEngine.from_plan(plan, model, data, batch_size=4,
                                        fanout=None)
    for a, b in zip(jax.tree_util.tree_leaves(engine.params),
                    jax.tree_util.tree_leaves(hist.meta["final_params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert engine.partition.num_parts == plan.comm.num_machines
    engine.submit(GNNRequest(uid=0, nodes=[0, 1, 5]))
    out = engine.run()
    assert len(out) == 1 and len(out[0].predictions) == 3
    assert engine.checkpoint_meta["extra"]["strategy"] == "llcg"

    with pytest.raises(ValueError, match="checkpoint_dir"):
        GNNServingEngine.from_plan(
            llcg_plan(dataclasses.replace(cfg, checkpoint_dir=None)),
            model, data)


# --------------------------------------------------------------------------
# 5. shard_map backend (multi-device subprocess)
# --------------------------------------------------------------------------
@pytest.mark.slow
def test_plan_backends_agree_including_new_compositions():
    """The canned LLCG plan AND all three new compositions
    (correction-every-m, hybrid halo→local, schedule-driven switch) lower
    onto shard_map and match the vmap backend's trajectory (same plan,
    same seeds, same byte accounting)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import numpy as np
from jax.sharding import Mesh
from repro.core import (DistConfig, ScheduleSpec, TrainPlan, averaging,
                        build_trainer, correction, halo_exchange, llcg_plan,
                        local_steps)
from repro.graph import sbm_graph
from repro.models.gnn import build_model

data = sbm_graph(num_nodes=120, num_classes=3, feature_dim=8,
                 feature_snr=0.4, homophily=0.9, seed=0)
model = build_model("GG", data.feature_dim, data.num_classes, hidden_dim=16)
cfg = DistConfig(num_machines=2, rounds=4, local_k=3, batch_size=8,
                 server_batch_size=16, fanout=5, correction_steps=1,
                 partition_method="random", seed=0)
specs = cfg.specs()
hybrid = TrainPlan(phases=(halo_exchange(first=2), local_steps(after=2),
                           averaging(after=2), correction(after=2)),
                   name="hybrid", seed=cfg.seed,
                   **{**specs, "schedule": ScheduleSpec(rounds=4, rho=1.0)})
big = lambda r, k: k >= 5
switch = TrainPlan(phases=(halo_exchange(when=lambda r, k: k < 5),
                           local_steps(when=big), averaging(when=big),
                           correction(when=big)),
                   name="switch", seed=cfg.seed,
                   **{**specs, "schedule": ScheduleSpec(rounds=3, rho=1.5)})
mesh = Mesh(np.asarray(jax.devices()[:2]), ("machine",))
out = {}
for name, plan in (("llcg", llcg_plan(cfg)),
                   ("corr_every_2", llcg_plan(cfg, correction_every=2)),
                   ("hybrid", hybrid), ("switch", switch)):
    hv = build_trainer(data, model, plan).run()
    hs = build_trainer(data, model, plan, backend="shard_map",
                       mesh=mesh).run()
    diff = max(
        float(abs(np.asarray(a) - np.asarray(b)).max())
        for a, b in zip(
            jax.tree_util.tree_leaves(hv.meta["final_params"]),
            jax.tree_util.tree_leaves(hs.meta["final_params"])))
    out[name] = {"max_diff": diff,
                 "bytes_equal": hv.bytes_cum == hs.bytes_cum,
                 "corr_rounds_equal":
                     hv.meta["corr_rounds"] == hs.meta["corr_rounds"],
                 "kinds": hs.meta["round_kinds"]}
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for name, res in out.items():
        assert res["max_diff"] < 1e-4, (name, res)
        assert res["bytes_equal"] and res["corr_rounds_equal"], (name, res)
    assert out["hybrid"]["kinds"] == ["ext", "ext", "local", "local"]
    assert "ext" in out["switch"]["kinds"] and \
        "local" in out["switch"]["kinds"]


# --------------------------------------------------------------------------
# 6. persistent compilation cache: one fixed directory per process
# --------------------------------------------------------------------------
_CACHE_CHILD = r"""
import json, jax, jax.numpy as jnp
from repro.core import enable_compilation_cache
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: hits.append(e) if e.endswith("/cache_hits") else None)
path = enable_compilation_cache()
if __import__("sys").argv[1] == "compile":
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(json.dumps({"path": path, "config": jax.config.jax_compilation_cache_dir,
                  "hits": len(hits)}))
"""


def _cache_child(mode: str, cache_env):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    proc = subprocess.run([sys.executable, "-c", _CACHE_CHILD, mode],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compilation_cache_uses_env_dir_and_hits_on_rerun(tmp_path):
    cache = tmp_path / "jax-cache"
    first = _cache_child("compile", cache)
    assert first["path"] == first["config"] == str(cache)
    assert first["hits"] == 0 and os.listdir(cache)
    assert _cache_child("compile", cache)["hits"] >= 1


def test_compilation_cache_defaults_to_fixed_checkout_dir():
    from repro.core.plan import DEFAULT_COMPILATION_CACHE_DIR
    out = _cache_child("no-compile", None)
    want = os.path.normpath(DEFAULT_COMPILATION_CACHE_DIR)
    assert out["path"] == out["config"] == want
    assert want == os.path.join(os.path.dirname(SRC), ".jax_cache")
