"""Mesh helpers over JAX's sharding-in-types surface.

The repo reads the ambient mesh (installed with ``jax.set_mesh``) as a
plain ``{axis: size}`` dict, and builds every mesh with ``Auto`` axis
types; these two helpers keep both in one place.
"""
from __future__ import annotations


import jax

__all__ = ["ambient_mesh_axes", "make_mesh"]


def ambient_mesh_axes() -> dict | None:
    """``{axis_name: size}`` of the ambient mesh, or None when meshless."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return None
    return dict(zip(m.axis_names, m.axis_sizes))


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis of type ``Auto``."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
