"""Production mesh construction.

A FUNCTION, not a module constant — importing this module must never touch
jax device state (the dry-run pins the device count before first jax use).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over (pure DP on 'pod' + FSDP 'data')."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def effective_batch_axes(mesh, global_batch: int) -> tuple:
    """Largest prefix of the batch axes whose product divides the batch —
    batch=1 long-context decode replicates instead of failing to tile."""
    axes = []
    prod = 1
    for a in batch_axes(mesh):
        if global_batch % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    return tuple(axes)
