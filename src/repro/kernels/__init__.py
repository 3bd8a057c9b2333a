"""Pallas TPU kernels for the solver's hot loop (DESIGN.md §3).

* ``diffusion`` — block-sparse (BSR) fluid push: the D-iteration hot loop
  recast as dense [bs x bs] tile matmuls on the MXU (the TPU-native
  replacement for the paper's scalar scatter push).  Three modules:

  * ``kernel.py`` — the ``pl.pallas_call`` + ``BlockSpec`` TPU kernels,
  * ``ops.py``    — the jit'd public wrappers (auto-interpret on CPU),
  * ``ref.py``    — the pure-jnp oracle used by tests and as the XLA
    fallback.

* ``tune`` — autotuning for those kernels: ``run_sweep`` measures the
  kernel knobs (bs, buffer_depth, occupancy threshold) on the current
  platform and records the winner, which ``solve(method="auto")`` and
  the session drivers read back.
"""
from . import diffusion  # noqa: F401
