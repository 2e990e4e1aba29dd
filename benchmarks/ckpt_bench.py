"""Checkpoint-overhead benchmark: what preemption safety costs per round.

The checkpoint manager (:mod:`repro.checkpoint.manager`) promises that
the training thread pays only the device→host snapshot — serialization,
fsync and the atomic rename happen on the writer thread.  This benchmark
measures that promise and records it in ``BENCH_ckpt.json`` at the repo
root under ``checkpoint_overhead``:

* ``save_stall`` — caller-thread duration of one ``CheckpointManager.
  save()`` on a multi-MB state tree, async vs sync, min over interleaved
  reps (the queue is drained between reps so backpressure never bites).
  ASSERTS the async stall is no worse than the sync stall — the writer
  thread must actually be taking the fsync off the training thread.
* ``round_overhead`` — end-to-end per-round cost of ``every=1``
  checkpointing on a real plan.  ``PlanTrainer.run()`` rebuilds its jit
  programs fresh per call, so raw walls are compile-dominated; instead
  each variant (no checkpoint / async / sync) runs a SHORT and a LONG
  schedule at identical shapes (ρ=1 → one trace) against a shared
  persistent compilation cache (warmed once), and the per-round time is
  the differenced wall ``(long − short)/Δrounds``, min over interleaved
  reps of every wall.  ASSERTS async-checkpointed round
  throughput ≥ 0.9× the no-checkpoint plan (one remeasure on a fresh
  seed, per the container noise discipline).

The bit-identity half of the checkpoint story — SIGKILL mid-schedule,
resume, byte-equal params — lives in ``tests/test_resume.py`` and the
``python -m repro.checkpoint.chaos`` harness, not here.
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Dict, List

import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import (
    CheckpointSpec, DistConfig, TrainPlan, averaging, build_trainer,
    enable_compilation_cache, local_steps,
)
from repro.graph import sbm_graph
from repro.models.gnn import build_model

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_ckpt.json")


def _state_tree(mb: float = 2.0, seed: int = 0) -> Dict:
    """Synthetic per-machine state sized like a real engine snapshot."""
    rng = np.random.default_rng(seed)
    n = max(1, int(mb * 1e6 / 4) // 8)
    return {
        "params": {f"w{i}": rng.standard_normal(n).astype(np.float32)
                   for i in range(6)},
        "opt": {f"m{i}": rng.standard_normal(n).astype(np.float32)
                for i in range(2)},
    }


def _bench_save_stall(reps: int = 5, mb: float = 2.0) -> Dict:
    """Caller-thread save() duration, async vs sync, min over reps."""
    tree = _state_tree(mb)
    payload = sum(int(a.nbytes)
                  for a in tree["params"].values()) + sum(
                      int(a.nbytes) for a in tree["opt"].values())
    stalls: Dict[str, List[float]] = {"sync": [], "async": []}
    with tempfile.TemporaryDirectory() as d:
        managers = {
            "sync": CheckpointManager(os.path.join(d, "s"), keep=2,
                                      async_=False),
            "async": CheckpointManager(os.path.join(d, "a"), keep=2,
                                       async_=True),
        }
        step = 0
        for _ in range(reps):
            for name, mgr in managers.items():
                step += 1
                t0 = time.perf_counter()
                mgr.save(step, tree, train={"round": step})
                stalls[name].append(time.perf_counter() - t0)
                # drain before the next rep: we are measuring the enqueue
                # stall, not queue backpressure
                mgr.wait()
        managers["async"].close()
    out = {
        "payload_mb": payload / 1e6,
        "reps": reps,
        "sync_stall_us": min(stalls["sync"]) * 1e6,
        "async_stall_us": min(stalls["async"]) * 1e6,
    }
    out["async_over_sync"] = out["async_stall_us"] / out["sync_stall_us"]
    assert out["async_stall_us"] <= out["sync_stall_us"], (
        f"async save() stalls the training thread LONGER than a "
        f"synchronous write ({out['async_stall_us']:.0f}us vs "
        f"{out['sync_stall_us']:.0f}us) — the writer thread is not "
        "taking the serialization off the caller")
    return out


def _setup(seed: int, rounds: int):
    # heavy enough that a round does real work (~100ms on this container):
    # the checkpoint tax is a fixed ~2-3ms per round (device→host snapshot
    # + History serialization on the training thread), so against trivial
    # rounds ANY checkpointing fails a relative throughput floor
    data = sbm_graph(num_nodes=1440, num_classes=4, feature_dim=32,
                     feature_snr=0.25, homophily=0.7, avg_degree=10,
                     seed=seed)
    model = build_model("GG", data.feature_dim, data.num_classes,
                        hidden_dim=128)
    cfg = DistConfig(num_machines=4, rounds=rounds, local_k=16,
                     batch_size=64, fanout=10, optimizer="sgd", lr=0.05,
                     partition_method="random", seed=seed)
    return data, model, cfg


def _measure_round_times(seed: int, reps: int, r_short: int,
                         r_long: int, ckdir: str) -> Dict[str, float]:
    data, model, _ = _setup(seed, rounds=r_long)
    # every run() rebuilds the jit programs; the persistent compilation
    # cache (shared across all variants — checkpointing never changes the
    # compiled HLO) turns recompiles into cheap low-variance cache hits so
    # the long−short difference isolates round execution
    def plan_for(rounds: int, variant: str) -> TrainPlan:
        _, _, cfg = _setup(seed, rounds)
        specs = cfg.specs()
        ck = None
        if variant != "none":
            ck = CheckpointSpec(dir=os.path.join(ckdir, variant), every=1,
                                keep=2, async_=(variant == "async"))
        return TrainPlan(phases=(local_steps(), averaging()),
                         name=f"ckpt-bench-{variant}", seed=seed,
                         checkpoint=ck, **specs)

    variants = ("none", "async", "sync")
    trainers = {(v, r): build_trainer(data, model, plan_for(r, v))
                for v in variants for r in (r_short, r_long)}
    walls: Dict = {k: [] for k in trainers}
    for trainer in trainers.values():          # warm-up: populate the
        trainer.run()                          # compilation cache
    for _ in range(reps):                      # interleaved: noise lands
        for key, trainer in trainers.items():  # evenly across variants
            t0 = time.perf_counter()
            trainer.run()
            walls[key].append(time.perf_counter() - t0)
    per_round = {}
    for v in variants:
        dt = min(walls[(v, r_long)]) - min(walls[(v, r_short)])
        per_round[v] = max(dt, 1e-9) / (r_long - r_short)
    return per_round


def _bench_round_overhead(reps: int = 4, r_short: int = 3,
                          r_long: int = 27, seed: int = 0,
                          throughput_floor: float = 0.9) -> Dict:
    """Per-round cost of every-round checkpointing, compile differenced."""
    with tempfile.TemporaryDirectory() as d:
        per_round = _measure_round_times(seed, reps, r_short, r_long, d)
    remeasured = False
    if per_round["none"] / per_round["async"] < throughput_floor:
        remeasured = True          # fresh seed: a noise excursion passes,
        with tempfile.TemporaryDirectory() as d:   # a real stall fails twice
            per_round = _measure_round_times(seed + 17, reps, r_short,
                                             r_long, d)
    out = {
        "reps": reps, "r_short": r_short, "r_long": r_long,
        "remeasured": remeasured, "throughput_floor": throughput_floor,
        "per_round_ms": {v: per_round[v] * 1e3 for v in per_round},
        "throughput_vs_none": {
            v: per_round["none"] / per_round[v] for v in per_round},
    }
    got = out["throughput_vs_none"]["async"]
    assert got >= throughput_floor, (
        f"async every-round checkpointing costs too much: round "
        f"throughput is {got:.2f}x the no-checkpoint plan "
        f"(floor {throughput_floor}x) — "
        f"{out['per_round_ms']['async']:.1f}ms/round vs "
        f"{out['per_round_ms']['none']:.1f}ms/round")
    return out


def bench_all() -> Dict:
    enable_compilation_cache()
    result = {"checkpoint_overhead": {
        "save_stall": _bench_save_stall(),
        "round_overhead": _bench_round_overhead(),
    }}
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=2)
    return result


def rows() -> List[Dict]:
    """CSV rows for benchmarks.run; writes ``BENCH_ckpt.json``."""
    sec = bench_all()["checkpoint_overhead"]
    stall, rnd = sec["save_stall"], sec["round_overhead"]
    return [
        {"name": "ckpt_async_save_stall",
         "us_per_call": stall["async_stall_us"],
         "derived": (f"sync={stall['sync_stall_us']:.0f}us;"
                     f"payload={stall['payload_mb']:.1f}MB")},
        {"name": "ckpt_round_overhead_async",
         "us_per_call": rnd["per_round_ms"]["async"] * 1e3,
         "derived": (f"vs_none={rnd['throughput_vs_none']['async']:.2f}x"
                     f"(>={rnd['throughput_floor']});"
                     f"sync={rnd['per_round_ms']['sync']:.1f}ms")},
    ]


if __name__ == "__main__":
    for r in rows():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    print(f"wrote {os.path.abspath(OUT_PATH)}")
