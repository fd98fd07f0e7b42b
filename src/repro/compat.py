"""The one place this repo reaches into ``jax`` for meshes and shard_map.

Every mesh axis is marked ``AxisType.Auto`` (the repo relies on auto
sharding propagation outside shard_map regions), and ``shard_map`` runs
with ``check_vma=False`` by default.  Import these two entry points from
here instead of from ``jax`` directly (``repro.analysis.lint`` enforces
it), so the choice is made once.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis marked ``AxisType.Auto``."""
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map``; ``check_vma`` defaults to False because the MoE
    bodies return replicated metrics computed via pmean, which the
    checker cannot always verify."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
