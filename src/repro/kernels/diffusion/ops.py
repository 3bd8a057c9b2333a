"""Public jit'd wrapper for the BSR diffusion push.

Chooses the Pallas kernel on TPU and interpret-mode / jnp oracle elsewhere,
and masks never-visited output row blocks (the kernel leaves them
uninitialised by design — revisiting-output accumulation only touches rows
that own at least one block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import bsr_spmm_pallas, frontier_round_bsr_pallas
from .ref import bsr_spmm_ref, csr_to_bsr

__all__ = ["bsr_spmm", "frontier_round_bsr", "prepare_bsr", "BsrMatrix"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@jax.tree_util.register_pytree_node_class
class BsrMatrix:
    """Host-prepared BSR operand: static structure + device arrays.

    A pytree (arrays are leaves, ``n_row_blocks``/``bs`` static), so a
    jitted round takes it as an argument and :meth:`from_arrays` can
    wrap ``jax.ShapeDtypeStruct`` leaves to lower a round without data.
    """

    def __init__(self, blocks, block_row, block_col, n_row_blocks, bs):
        self.blocks = jnp.asarray(blocks)
        self.block_row = jnp.asarray(block_row, dtype=jnp.int32)
        self.block_col = jnp.asarray(block_col, dtype=jnp.int32)
        self.n_row_blocks = int(n_row_blocks)
        self.bs = int(bs)
        occ = np.zeros(n_row_blocks, dtype=bool)
        occ[np.asarray(block_row)] = True
        self.row_occupied = jnp.asarray(occ)

    @classmethod
    def from_arrays(cls, blocks, block_row, block_col, row_occupied,
                    n_row_blocks, bs) -> "BsrMatrix":
        """Wrap ready leaves as they are (device arrays, tracers or
        ``jax.ShapeDtypeStruct``); no conversion, no upload."""
        m = object.__new__(cls)
        m.blocks, m.block_row, m.block_col = blocks, block_row, block_col
        m.row_occupied = row_occupied
        m.n_row_blocks, m.bs = int(n_row_blocks), int(bs)
        return m

    def tree_flatten(self):
        return ((self.blocks, self.block_row, self.block_col,
                 self.row_occupied), (self.n_row_blocks, self.bs))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls.from_arrays(*children, *aux)

    @property
    def n_blocks(self) -> int:
        return int(self.blocks.shape[0])

    @property
    def density(self) -> float:
        return self.n_blocks / max(self.n_row_blocks**2, 1)


def prepare_bsr(indptr, indices, weights, n, bs=128) -> BsrMatrix:
    blocks, br, bc, nrb = csr_to_bsr(
        np.asarray(indptr), np.asarray(indices), np.asarray(weights), n, bs
    )
    return BsrMatrix(blocks, br, bc, nrb, bs)


def bsr_spmm(
    m: BsrMatrix,
    x: jax.Array,  # [n_col_blocks*bs] or [n_col_blocks*bs, C]
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """delta = P @ x with P in BSR form.  Returns same leading shape as x."""
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    c = x.shape[1]
    xt = x.reshape(-1, m.bs, c)
    if use_pallas is None:
        use_pallas = True
    if interpret is None:
        interpret = not _on_tpu()
    if use_pallas:
        out = bsr_spmm_pallas(
            m.blocks, m.block_row, m.block_col, xt, m.n_row_blocks,
            bs=m.bs, interpret=interpret,
        )
        out = jnp.where(m.row_occupied[:, None, None], out, 0.0)
    else:
        out = bsr_spmm_ref(
            m.blocks, m.block_row, m.block_col, xt, m.n_row_blocks
        )
    out = out.reshape(-1, c)
    return out[:, 0] if squeeze else out


def frontier_round_bsr(
    m: BsrMatrix,
    f: jax.Array,  # [n] or [n, C] residual fluid, n = n_row_blocks * bs
    w: jax.Array,  # [n] selection weights (0 = padding / inert slot)
    t: jax.Array,  # scalar threshold (traced value is fine)
    *,
    backend: str | None = None,  # None/"auto" | "pallas" | "block"
    interpret: bool | None = None,
    buffer_depth: int = 1,
    occupancy_threshold: float = 0.0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused frontier round ``F' = F - sent + P @ sent`` over BSR ``m``.

    ``sent = where(|F| * w > t, F, 0)`` — every node above the threshold
    diffuses simultaneously (the frontier-batched D-iteration schedule).
    Returns ``(f_new, sent, res)`` with ``res = |f_new|_1`` (scalar).

    ``buffer_depth`` (pallas backend only) selects the tile-fetch pipeline
    depth — pure data movement, bit-identical results across depths.

    ``occupancy_threshold`` defers sparse block columns: a column block whose
    fraction of above-threshold nodes is <= the threshold keeps its fluid
    this round and diffuses later (the D-iteration schedule permits any
    subset of nodes to fire per round, so this is exact, not approximate —
    deferred fluid is *kept*, never dropped).  0.0 (default) arms every
    column with at least one above-threshold node, the historical behaviour.

    Backends:

    * ``pallas`` — the fused TPU kernel: masking, the block-column occupancy
      skip, and the per-row residual reduction run inside one grid sweep;
      block rows with no tiles fall back to the kept fluid via the
      row-occupancy epilogue (the kernel leaves them uninitialised).
    * ``block`` — jnp oracle (einsum over tiles + segment-sum), the fast
      path on CPU where interpret-mode Pallas is emulation-speed.
    * ``auto``/None — pallas on TPU, block elsewhere.
    """
    squeeze = f.ndim == 1
    f2 = f[:, None] if squeeze else f
    c = f2.shape[1]
    if backend in (None, "auto"):
        backend = "pallas" if _on_tpu() else "block"
    if backend == "pallas":
        # the kernel folds the threshold into the weights (wt = w/t, select
        # when |f|*wt > 1); the wrapper MUST use the identical rounded
        # predicate or a boundary node could be "sent" by one side and
        # "kept" by the other, double-counting or losing its fluid.
        wt_flat = (w / t).astype(f2.dtype)
        sel = jnp.abs(f2) * wt_flat[:, None] > 1.0
    else:
        sel = jnp.abs(f2) * w[:, None] > t
    blk = sel.reshape(-1, m.bs * c)
    if occupancy_threshold > 0.0:
        frac = jnp.mean(blk.astype(f2.dtype), axis=1)
        col_active = (frac > occupancy_threshold).astype(jnp.int32)
        # only nodes in armed columns fire; the rest keep their fluid.
        sel = jnp.logical_and(
            sel, (col_active != 0).repeat(m.bs)[:, None]
        )
    else:
        col_active = jnp.any(blk, axis=1).astype(jnp.int32)
    sent = jnp.where(sel, f2, jnp.zeros_like(f2))
    if backend == "block":
        xt = sent.reshape(-1, m.bs, c)
        delta = bsr_spmm_ref(
            m.blocks, m.block_row, m.block_col, xt, m.n_row_blocks
        )
        f_new = (f2 - sent) + delta.reshape(f2.shape)
        res = jnp.sum(jnp.abs(f_new))
    elif backend == "pallas":
        if interpret is None:
            interpret = not _on_tpu()
        ft = f2.reshape(-1, m.bs, c)
        wt = wt_flat.reshape(-1, m.bs, 1)
        out, row_l1 = frontier_round_bsr_pallas(
            m.blocks.astype(f2.dtype), m.block_row, m.block_col, col_active,
            ft, wt, m.n_row_blocks, bs=m.bs, interpret=interpret,
            buffer_depth=buffer_depth,
        )
        # rows owning no block never get their output tile initialised:
        # substitute the kept fluid (F - sent) and its |·|_1 there.
        keep = (f2 - sent).reshape(-1, m.bs, c)
        occ = m.row_occupied
        f_new = jnp.where(occ[:, None, None], out, keep).reshape(f2.shape)
        keep_l1 = jnp.sum(jnp.abs(keep), axis=(1, 2))
        res = jnp.sum(jnp.where(occ, row_l1[:, 0, 0], keep_l1))
    else:
        raise ValueError(f"unknown frontier backend {backend!r}")
    if squeeze:
        return f_new[:, 0], sent[:, 0], res
    return f_new, sent, res
