"""Production mesh definitions.

Functions, not module-level constants — importing this module never touches
jax device state.  The dry-run (and only the dry-run) forces 512 host
devices via XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: model code hints shardings
    with ``with_sharding_constraint`` and leaves propagation to XLA."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None):
    """Small local mesh over however many devices exist (smoke/serving)."""
    n = n_devices or len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
