"""Mesh factories for the production topology.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is the DCN dimension; batch (pure DP) shards over it so the
only cross-pod collective in steady state is the gradient all-reduce.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Tuple

import jax


def _mesh_kwargs(n: int) -> dict:
    """Every mesh axis is Auto (compiler-chosen partitioning)."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many real devices exist (tests/examples)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    return jax.make_mesh((n // model, model), ("data", "model"),
                         **_mesh_kwargs(2))


def data_axes(mesh) -> Tuple[str, ...]:
    """The batch (pure-DP) axes: ('pod', 'data') on multi-pod meshes."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh, *names: str) -> int:
    total = 1
    for n in names:
        if n in mesh.axis_names:
            total *= mesh.shape[n]
    return total
