"""The few spellings of the JAX surface the framework settles in one place.

Written for the installed JAX (0.9): ``jax.shard_map`` with ``check_vma`` /
``axis_names``, ``jax_num_cpu_devices``, the abstract mesh's
``manual_axes``. Nothing here branches on a version. ``dslint`` DSL003
keeps ``jax.experimental.shard_map`` out of the tree; every internal caller
takes :func:`shard_map` from here.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh=None, in_specs=None, out_specs=None,
              check_vma=True, axis_names=()):
    """``jax.shard_map`` with the mesh accepted positionally.
    ``axis_names`` is the MANUAL axis set (any iterable; empty = every mesh
    axis is manual)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma,
                         axis_names=frozenset(axis_names))


def request_cpu_devices(n: int) -> None:
    """Ask for ``n`` virtual CPU devices. Must run BEFORE the backend
    initializes."""
    jax.config.update("jax_num_cpu_devices", n)


def manual_axes():
    """Axis names currently mapped manually (non-empty exactly when we are
    tracing inside a ``shard_map`` body)."""
    return tuple(jax.sharding.get_abstract_mesh().manual_axes)
