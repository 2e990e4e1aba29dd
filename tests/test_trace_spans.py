"""The ``llcg.*`` host spans and the two named round programs, read back
from a profiler trace of a tiny LLCG run, and a traced run's trajectory
against an untraced one."""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import DistConfig, build_trainer, llcg_plan
from repro.graph import sbm_graph
from repro.models.gnn import build_model

ROUNDS = 3


@pytest.fixture(scope="module")
def tiny():
    data = sbm_graph(num_nodes=120, num_classes=3, feature_dim=8,
                     feature_snr=0.4, homophily=0.9, avg_degree=6, seed=2)
    model = build_model("GBG", data.feature_dim, data.num_classes,
                        hidden_dim=16)
    return data, model


def _plan(placement):
    plan = llcg_plan(DistConfig(num_machines=2, rounds=ROUNDS, local_k=2,
                                correction_steps=1, batch_size=8,
                                server_batch_size=16, fanout=4,
                                partition_method="random", seed=5))
    return dataclasses.replace(
        plan, sampler=dataclasses.replace(plan.sampler, placement=placement))


def _host_events(log_dir):
    """(name, start, end, stats) of every event on the host planes; the
    stats of the ``llcg.round`` spans only."""
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats) if e.name == "llcg.round" else {})
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("placement", ["host", "device"])
def test_spans_and_programs_in_the_trace(tiny, tmp_path, placement):
    data, model = tiny
    plain = build_trainer(data, model, _plan(placement)).run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        traced = build_trainer(data, model, _plan(placement)).run()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    named = lambda n: [e for e in events if e[0] == n]  # noqa: E731

    rounds = named("llcg.round")
    assert sorted(e[3]["step_num"] for e in rounds) == list(
        range(1, ROUNDS + 1))
    for rnd in rounds:
        reads = [e for e in named("llcg.read") if _inside(e, rnd)]
        assert len(reads) >= 3      # local loss, correction loss, evaluation
        for n in ("llcg.dispatch", "llcg.evaluate"):
            assert len([e for e in named(n) if _inside(e, rnd)]) == 1
    draws = named("llcg.correction_draw")
    assert len(draws) == ROUNDS
    assert all(any(_inside(d, s) for s in named("llcg.sample"))
               for d in draws)
    assert all(any(_inside(r, ev) for r in named("llcg.read"))
               for ev in named("llcg.evaluate"))
    assert named("PjitFunction(counted_round)")
    assert named("PjitFunction(counted_correction)")

    assert traced.val_score == plain.val_score
    assert traced.train_loss == plain.train_loss
    for key in ("local_loss", "corr_loss", "corr_rounds"):
        assert traced.meta[key] == plain.meta[key]
    for a, b in zip(jax.tree_util.tree_leaves(traced.meta["final_params"]),
                    jax.tree_util.tree_leaves(plain.meta["final_params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
