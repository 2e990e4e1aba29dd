"""End-to-end trainer: LLCG (or fully-sync) over any registered architecture.

Production path: ``--arch <id> --mesh production`` on a real TPU slice.
On this CPU container the same code runs reduced configs on the host mesh —
``examples/distributed_lm_llcg.py`` drives it for the e2e demo.

The loop implements Algorithm 2 end-to-end: per round r it runs K·ρ^r local
steps on every LLCG group (one lowered round-step program; K is bucketed to
powers of two so retraces stay bounded), averages, corrects with S global
steps, checkpoints, and logs the exact byte accounting the paper reports.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config, get_smoke_config
from repro.core.schedules import local_epoch_schedule
from repro.data.tokens import TokenDataset, synthetic_corpus
from repro.distributed.sharding import param_pspecs, batch_pspec, group_axis_for
from repro.distributed.steps import LLCGStepConfig, build_llcg_round_step
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.transformer.model import LM
from repro.optim import adamw
from repro.utils.logging import get_logger, Timer
from repro.utils.pytree import tree_bytes

log = get_logger("train")


@dataclasses.dataclass
class TrainConfig:
    arch: str = "gemma3-1b"
    smoke: bool = True               # reduced config (CPU-friendly)
    rounds: int = 8
    base_k: int = 2                  # K
    rho: float = 1.3                 # ρ
    correction_steps: int = 1        # S
    batch_per_group: int = 4
    seq_len: int = 128
    lr: float = 3e-4
    server_lr: float = 1e-4
    heterogeneity: float = 0.6
    seed: int = 0
    ckpt_dir: Optional[str] = None
    mesh: str = "host"               # host | production | production-multipod
    model_parallel: int = 1


def make_mesh(cfg: TrainConfig):
    if cfg.mesh == "production":
        return make_production_mesh(multi_pod=False)
    if cfg.mesh == "production-multipod":
        return make_production_mesh(multi_pod=True)
    return make_host_mesh(model_parallel=cfg.model_parallel)


def train(cfg: TrainConfig):
    mesh = make_mesh(cfg)
    gaxis = group_axis_for(mesh)
    G = mesh.shape[gaxis]
    mcfg = get_smoke_config(cfg.arch) if cfg.smoke else get_config(cfg.arch)
    model = LM(mcfg)
    log.info("arch=%s G=%d mesh=%s layers=%d d=%d", mcfg.name, G,
             dict(mesh.shape), mcfg.num_layers, mcfg.d_model)

    corpus = synthetic_corpus(mcfg.vocab_size, num_shards=G,
                              tokens_per_shard=max(cfg.seq_len * 64, 20_000),
                              heterogeneity=cfg.heterogeneity, seed=cfg.seed)
    rng = np.random.default_rng(cfg.seed)

    with mesh:
        params = jax.jit(model.init)(jax.random.PRNGKey(cfg.seed))
        local_opt, server_opt = adamw(cfg.lr), adamw(cfg.server_lr)
        params_G = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), params)
        opt_G = jax.vmap(local_opt.init)(params_G)
        server_state = server_opt.init(params)
        param_mb = tree_bytes(params) / 1e6

        schedule = local_epoch_schedule(cfg.base_k, cfg.rho, cfg.rounds)
        step_cache = {}
        bytes_cum = 0.0
        for r, k_r in enumerate(schedule, start=1):
            k_pow2 = 1 << (k_r - 1).bit_length()   # bucket K → bounded retraces
            if k_pow2 not in step_cache:
                step_cache[k_pow2] = jax.jit(build_llcg_round_step(
                    model, local_opt, server_opt,
                    LLCGStepConfig(num_groups=G, local_steps=k_pow2,
                                   correction_steps=cfg.correction_steps)))
            round_step = step_cache[k_pow2]

            local = _local_batches(corpus, G, k_pow2, cfg, rng)
            corr = _corr_batches(corpus, cfg, rng)
            with Timer() as t:
                params_G, opt_G, server_state, metrics = round_step(
                    params_G, opt_G, server_state, local, corr)
                jax.block_until_ready(metrics["local_loss"])
            bytes_cum += 2 * G * param_mb  # up + down, MB
            log.info("round %2d K=%3d local_loss=%.4f corr_loss=%.4f "
                     "%.2fs comm=%.1fMB", r, k_pow2,
                     float(metrics["local_loss"]),
                     float(metrics["corr_loss"]), t.elapsed, bytes_cum)
            if cfg.ckpt_dir:
                avg = jax.tree_util.tree_map(lambda x: np.asarray(x[0]),
                                             params_G)
                save_checkpoint(cfg.ckpt_dir, r, avg,
                                extra={"round": r, "comm_mb": bytes_cum})
        return params_G, metrics


# --------------------------------------------------------------------------
# Preemption-safe resume for plan-API (GNN) runs
# --------------------------------------------------------------------------
def resume(data, model, plan, ckpt_dir: Optional[str] = None,
           step: Optional[int] = None, backend: str = "vmap", mesh=None):
    """Resume a checkpointed :class:`repro.core.plan.TrainPlan` run.

    Restores the latest VALID checkpoint (or ``step``) under ``ckpt_dir``
    (default: ``plan.checkpoint.dir``) — full state: params, optimizer
    states, comm residual, RNG streams, schedule cursor, History — and
    continues training mid-schedule, bit-identical to a run that was never
    interrupted.  Refuses checkpoints whose plan/backend or dataset digest
    does not match.  Returns the completed ``History``.
    """
    from repro.core.plan import build_trainer
    if ckpt_dir is None:
        if plan.checkpoint is None:
            raise ValueError("resume needs a checkpoint directory: pass "
                             "ckpt_dir= or set plan.checkpoint")
        ckpt_dir = plan.checkpoint.dir
    trainer = build_trainer(data, model, plan, backend=backend, mesh=mesh)
    return trainer.run(resume_from=ckpt_dir, resume_step=step)


def run_or_resume(data, model, plan, backend: str = "vmap", mesh=None):
    """Preemption-safe entry: resume if a valid checkpoint exists, else run.

    The idempotent form a preemptible job wants — the SAME command line
    works for the first launch and for every relaunch after a kill
    (``repro.checkpoint.chaos`` drives it under SIGKILL).  Requires
    ``plan.checkpoint``.
    """
    from repro.checkpoint.manager import CheckpointManager
    from repro.core.plan import build_trainer
    if plan.checkpoint is None:
        raise ValueError("run_or_resume requires plan.checkpoint "
                         "(a CheckpointSpec)")
    have = CheckpointManager(plan.checkpoint.dir, keep=0,
                             async_=False).latest_step()
    trainer = build_trainer(data, model, plan, backend=backend, mesh=mesh)
    if have is None:
        return trainer.run()
    return trainer.run(resume_from=plan.checkpoint.dir)


def _local_batches(corpus: TokenDataset, g: int, k: int, cfg: TrainConfig,
                   rng) -> dict:
    toks = np.zeros((g, k, cfg.batch_per_group, cfg.seq_len), np.int32)
    labs = np.zeros_like(toks)
    for s in range(g):
        stream = corpus.tokens[s % corpus.num_shards]
        for i in range(k):
            starts = rng.integers(0, stream.size - cfg.seq_len - 1,
                                  cfg.batch_per_group)
            toks[s, i] = np.stack([stream[a:a + cfg.seq_len] for a in starts])
            labs[s, i] = np.stack([stream[a + 1:a + cfg.seq_len + 1]
                                   for a in starts])
    return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}


def _corr_batches(corpus: TokenDataset, cfg: TrainConfig, rng) -> dict:
    s_steps = cfg.correction_steps
    bsz = cfg.batch_per_group * 2
    toks = np.zeros((s_steps, bsz, cfg.seq_len), np.int32)
    labs = np.zeros_like(toks)
    for i in range(s_steps):
        for b in range(bsz):
            stream = corpus.tokens[rng.integers(corpus.num_shards)]
            a = rng.integers(0, stream.size - cfg.seq_len - 1)
            toks[i, b] = stream[a:a + cfg.seq_len]
            labs[i, b] = stream[a + 1:a + cfg.seq_len + 1]
    return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainConfig):
        kind = type(f.default) if f.default is not None else str
        if kind is bool:
            ap.add_argument(f"--{f.name.replace('_','-')}", type=lambda s: s.lower() in ("1","true","yes"),
                            default=f.default)
        else:
            ap.add_argument(f"--{f.name.replace('_','-')}",
                            type=kind if f.default is not None else str,
                            default=f.default)
    args = ap.parse_args(argv)
    cfg = TrainConfig(**{f.name: getattr(args, f.name)
                         for f in dataclasses.fields(TrainConfig)})
    from repro.core.plan import enable_compilation_cache
    enable_compilation_cache()
    train(cfg)


if __name__ == "__main__":
    main()
