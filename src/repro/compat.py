"""The repo's one door to JAX's mesh and shard_map API (DESIGN.md §8).

Every mesh/shard_map construction in the repo goes through this module;
nothing else calls ``jax.make_mesh`` / ``jax.shard_map`` directly, so the
defaults below (all-Auto mesh axes, keyword-only shard_map) hold
everywhere. Written for the installed JAX (>= 0.9).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: F401  (re-export)


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis Auto; `devices` pins the device list
    (e.g. a described TPU topology's devices for an ahead-of-time
    compile)."""
    axes = tuple(axes)
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """Keyword-only `jax.shard_map`; `check_vma=None` keeps JAX's default."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
