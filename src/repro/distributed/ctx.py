"""Ambient mesh context so model code can hint shardings without
hard-coding a mesh (single-device tests run with no mesh at all).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


_MESH: contextvars.ContextVar = contextvars.ContextVar("repro_mesh", default=None)


def current_mesh():
    return _MESH.get()


@contextlib.contextmanager
def use_mesh(mesh):
    """Set the ambient mesh for model sharding hints AND jax's context."""
    token = _MESH.set(mesh)
    try:
        with mesh:   # jax.sharding.Mesh is a context manager
            yield mesh
    finally:
        _MESH.reset(token)


def batch_axes() -> Tuple[str, ...]:
    """Mesh axes that shard the batch (every non-'model' axis)."""
    mesh = current_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names if a != "model")


def wsc(x, *spec) -> jax.Array:
    """with_sharding_constraint if a mesh is ambient, else identity.

    Axis names not present in the current mesh are dropped from the spec,
    so model code can always hint P(("pod","data"), None, "model").
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    cleaned = P(*[keep(e) for e in spec])
    return jax.lax.with_sharding_constraint(x, cleaned)
