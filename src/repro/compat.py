"""Mesh helpers over jax's sharding API (jax >= 0.9).

Nothing here imports repro modules — safe to import from anywhere.
"""
from __future__ import annotations

import contextlib

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis Auto (jax defaults to Explicit):
    the executor's shard_maps and ``with_sharding_constraint`` calls rely
    on GSPMD propagation outside them."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh
    (``jax.set_mesh``); a no-op for ``None``."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def current_mesh():
    """The ambient mesh installed by :func:`use_mesh`, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m


def auto_axis_names(mesh):
    """Names of mesh axes usable for *automatic* sharding right now (inside
    a shard_map every axis is Manual)."""
    if mesh is None:
        return None
    types = dict(zip(mesh.axis_names, mesh.axis_types))
    return tuple(a for a, t in types.items()
                 if t != jax.sharding.AxisType.Manual)
