"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked on first backend init — the dry-run sets
``xla_force_host_platform_device_count`` before any jax import).

Target hardware: TPU v5e pods — 256 chips/pod (16×16 ICI torus), 2 pods.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the models place arrays through GSPMD sharding hints, and
    # jax.make_mesh's default Explicit axes would type every gather and
    # reshape by its sharding instead
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host actually has — used by examples/tests on CPU."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))
