"""Mesh builders (functions, not module constants — importing this module
never touches jax device state)."""
from __future__ import annotations

from repro import compat


def make_mesh(shape, axes):
    return compat.make_mesh(shape, axes)


def submesh(mesh, world: int, model_axis: str = "model"):
    """Sub-mesh over the first `world` ranks of `mesh`'s model axis —
    the slicing every per-world geometry (executor meshes, sized-layout
    step fns) derives from, so a "tp@4" run on an 8-rank launch uses a
    true 4-rank SPMD mesh in-process."""
    import numpy as np
    from jax.sharding import Mesh
    if not 0 < world <= mesh.shape[model_axis]:
        raise ValueError(f"world {world} not in 1..{mesh.shape[model_axis]}")
    ax = mesh.axis_names.index(model_axis)
    dev = mesh.devices.take(np.arange(world), axis=ax)
    return Mesh(dev, mesh.axis_names)
