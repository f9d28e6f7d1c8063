"""The few jax spellings this repo wraps, in one place.

Written for the jax release ``pyproject.toml`` pins (0.9.x); there is no
branch for any other release.  Call sites import the symbol from here so
a future API move is a one-file change.
"""
from __future__ import annotations

import jax
from jax import shard_map  # noqa: F401  (re-exported for repro.sparse)


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off.

    Used for per-shard-independent bodies (no collectives), where the
    checker only costs trace time.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` for Pallas TPU kernels."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis of type Auto.

    ``jax.make_mesh`` defaults its axes to Explicit, under which a ``jit``
    over a multi-device mesh needs a ``jax.set_mesh`` context; the
    ``shard_map`` bodies here spell their partitioning out themselves.
    """
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
