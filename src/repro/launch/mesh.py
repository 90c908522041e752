"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count BEFORE first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ('data', 'model').
    Multi-pod:  2x16x16 = 512 chips ('pod', 'data', 'model')."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1):
    """Debug mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
