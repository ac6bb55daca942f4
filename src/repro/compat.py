"""Mesh and ``shard_map`` helpers shared by every sharded program (jax 0.9).

  * :func:`make_mesh` builds a mesh whose axes are all ``AxisType.Auto``
    (the compiler propagates shardings, as every call site expects).
  * :func:`shard_map` calls ``jax.shard_map`` with ``check_vma=False``.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, *, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off.

    The repo's sharded programs are strictly lane-local (no collectives):
    a lane-local ``scan`` carry initialised from a closed-over constant is
    unvarying on entry and lane-varying on exit, which ``check_vma=True``
    rejects although no value crosses a lane.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
