"""Stable names over the JAX mesh/sharding surface.

Callers import these names instead of the JAX attributes directly, so a
future change of the JAX surface is absorbed here:

* ``shard_map``       — ``jax.shard_map``.
* ``make_mesh``       — an explicit all-Auto mesh.
* ``set_mesh``        — ``jax.set_mesh``.
* ``abstract_mesh``   — the ambient mesh's abstract view or None.
* ``shard_hint``      — with_sharding_constraint against the ambient mesh.
* ``enable_x64``      — a context manager turning on 64-bit types.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, PartitionSpec

shard_map = jax.shard_map


def make_mesh(shape, axes):
    """An explicit all-Auto mesh."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def set_mesh(mesh) -> None:
    """Install the mesh context used by activation sharding constraints."""
    jax.set_mesh(mesh)


def abstract_mesh():
    """The ambient mesh (abstract view), or None outside any mesh context."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or not m.axis_names else m


def auto_axis_names(mesh) -> set:
    """Mesh axes eligible for sharding constraints (Auto axes)."""
    return {n for n, t in zip(mesh.axis_names, mesh.axis_types) if t == AxisType.Auto}


def axis_size(name) -> int:
    """Static size of a mapped mesh axis."""
    return jax.lax.axis_size(name)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a flat dict."""
    return compiled.cost_analysis()


def shard_hint(x, spec: PartitionSpec):
    """``with_sharding_constraint(x, spec)`` against the ambient mesh."""
    return jax.lax.with_sharding_constraint(x, spec)


def enable_x64():
    """Context manager enabling float64/int64 inside its block."""
    return jax.enable_x64(True)
