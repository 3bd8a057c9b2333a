"""Thin spellings of the JAX sharding APIs the repo uses (JAX 0.9).

* :func:`shard_map` — ``jax.shard_map`` with ``mesh``/``in_specs``/
  ``out_specs`` passed positionally as well as by name.
* :func:`make_mesh` / :func:`mesh_axis_types_kw` — meshes with every
  axis in ``AxisType.Auto`` (sharding propagation, no explicit axes).
* :func:`cost_analysis_dict` — ``compiled.cost_analysis()`` with a
  missing analysis as ``{}``.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map", "make_mesh", "mesh_axis_types_kw",
           "cost_analysis_dict"]


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()``; a missing analysis is ``{}``."""
    return compiled.cost_analysis() or {}


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def mesh_axis_types_kw(n_axes: int) -> dict:
    """``{"axis_types": (Auto,) * n_axes}`` for ``Mesh``/``make_mesh``."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_mesh(axis_shapes, axis_names, **kwargs):
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)
