"""The distributed layer's spellings of JAX's mesh/shard_map surface.

Written for the installed JAX (0.9): ``jax.shard_map`` with ``check_vma``,
``jax.lax.axis_size``, ``jax.extend.core`` and explicit mesh axis types.
Everything that crosses that surface goes through these few names, so a
future API move is one edit here instead of one per call site.
"""
from __future__ import annotations

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def axis_size(axis_name) -> int:
    """Static size of a named mesh axis from inside shard_map."""
    return jax.lax.axis_size(axis_name)


def jaxpr_types() -> tuple:
    """(Jaxpr, ClosedJaxpr) classes, for the collective counter's jaxpr walk
    (``core.distributed.count_collectives``)."""
    return Jaxpr, ClosedJaxpr


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` over ``jax.devices()``, every axis Auto-sharded."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )
