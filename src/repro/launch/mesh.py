"""Production mesh for the engine's dry run (DESIGN.md §6).

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.
``repro.launch.dryrun.lower_solver`` flattens either mesh into the
engine's one ``pid`` axis.

Defined as a function so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialisation).
"""
from __future__ import annotations

__all__ = ["make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    from repro.parallel.compat import make_mesh, mesh_axis_types_kw

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, **mesh_axis_types_kw(len(axes)))
