"""Faults planted under the timed path, for the benchmark's own checks that
a broken program reads as not correct (``test_bench_faults.py``, and
``calibrate.py`` on the chip, which reads each fault's numbers).

Each is a context manager that breaks the program while it is active:

* ``state_unchanged``: every round returns the weights it was given;
* ``half_batch``: half of every local and correction batch is left out of
  the loss, whose mean is taken over the rest;
* ``exchange_left_out``: the averaging takes machine 0's weights instead
  of the mean over machines (vmap), or skips the all-reduce (shard_map);
* ``altered_answer``: one entry of every round's sampled neighbor table is
  changed where the sampler produces it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import types


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def state_unchanged():
    import repro.core.plan as plan_mod
    real = plan_mod._PlanProgram.run_round

    def run_round(self, state, feats, labels, inputs):
        _, metrics = real(self, state, feats, labels, inputs)
        return state, metrics

    with _patched(plan_mod._PlanProgram, "run_round", run_round):
        yield


def _resampled(change):
    """Wrap ``RoundSampler.sample`` so each round's inputs pass through
    ``change(inputs) -> inputs``."""
    import repro.core.plan as plan_mod
    real = plan_mod.RoundSampler.sample

    def sample(self, desc, k_pad=None):
        return change(real(self, desc, k_pad))

    return _patched(plan_mod.RoundSampler, "sample", sample)


@contextlib.contextmanager
def half_batch():
    def change(inp):
        bm = inp.bmasks
        half = bm.shape[-1] // 2
        inp = dataclasses.replace(inp, bmasks=bm.at[..., :half].set(0.0))
        if inp.corr_bmasks is not None:
            cb = inp.corr_bmasks
            inp = dataclasses.replace(
                inp, corr_bmasks=cb.at[..., :cb.shape[-1] // 2].set(0.0))
        return inp

    with _resampled(change):
        yield


@contextlib.contextmanager
def altered_answer():
    def change(inp):
        t = inp.tables
        return dataclasses.replace(inp, tables=t.at[0, 0, 0, 0].set(
            t[0, 0, 0, 0] + 1))

    with _resampled(change):
        yield


@contextlib.contextmanager
def exchange_left_out():
    """The engine's module sees a ``jnp`` whose mean over the machine axis
    returns machine 0, and a ``jax.lax`` whose ``pmean`` returns its
    input unchanged: the averaging collective is left out."""
    import jax
    import jax.numpy as jnp
    import repro.core.engine as engine

    def mean(x, axis=None, **kw):
        return x[0] if axis == 0 else jnp.mean(x, axis=axis, **kw)

    fake_jnp = types.SimpleNamespace(**{k: getattr(jnp, k)
                                        for k in dir(jnp)
                                        if not k.startswith("__")})
    fake_jnp.mean = mean
    fake_lax = types.SimpleNamespace(**{k: getattr(jax.lax, k)
                                        for k in dir(jax.lax)
                                        if not k.startswith("__")})
    fake_lax.pmean = lambda x, axis_name, **kw: x
    fake_jax = types.SimpleNamespace(**{k: getattr(jax, k)
                                        for k in dir(jax)
                                        if not k.startswith("__")})
    fake_jax.lax = fake_lax
    with _patched(engine, "jnp", fake_jnp), _patched(engine, "jax",
                                                     fake_jax):
        yield


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "exchange_left_out": exchange_left_out,
          "altered_answer": altered_answer}
