"""Block-sparse (BSR) fluid-push kernel — the D-iteration hot loop on TPU.

The paper's elementary operation is a scalar push ``F[j] += sent * P[j, i]``.
A TPU has no efficient scalar scatter; the TPU-native adaptation (DESIGN.md
§3) preprocesses P into Block-Sparse-Row form — ``n_blocks`` dense
``[bs, bs]`` tiles, each tagged with its (block_row, block_col) — and turns
one frontier round into a sequence of dense tile matmuls on the MXU:

    delta[block_row] += P_block @ sent[block_col]

Grid: one step per nonzero block, sorted by block_row.  The output tile for
a block row stays resident in VMEM across all its blocks (revisiting output
pattern); it is zero-initialised on first visit.  Block coordinates arrive
via scalar prefetch (``PrefetchScalarGridSpec``) so the BlockSpec index_maps
can route HBM→VMEM DMAs for exactly the tiles the sparse structure touches.

Supports a multi-source right-hand side ``x: [n_col_blocks*bs, C]`` so many
diffusion vectors (e.g. personalized-PageRank columns) share one sweep of
the sparse structure; ``C = 1`` is the paper's case but wider C raises
arithmetic intensity from O(1) to O(C) per weight byte.

Both kernels the solvers run walk a per-round *visit list* instead of
the whole tile pool, the grid's length being the round's visit count, a
traced value.  The fused frontier round (``frontier_round_bsr_pallas``)
visits the tiles of the armed block columns (those holding fluid above
threshold), and rows without a visit take the kept fluid in the ops.py
wrapper.  The engine's gather kernel (``bsr_gather_spmm_pallas``) visits
the tiles of the source rows that send fluid, and the engine masks the
rows no visit reaches.  Tiles that would multiply zero fluid cost no
step, no byte and no MXU issue slot.

DMA pipelining (``buffer_depth``): the tile pool is the dominant byte
stream (``bs*bs`` weights vs ``bs*C`` fluid per step, and C is small).
With ``buffer_depth == 1`` the tile fetch rides Pallas's automatic
double-buffered BlockSpec pipeline.  With ``buffer_depth >= 2`` the tile
operand stays in HBM (``memory_space=ANY``) and the kernel rotates manual
async copies through a ``[depth, bs, bs]`` VMEM ring: step ``i`` computes
out of slot ``i % depth`` while the DMAs for steps ``i+1 .. i+depth-1``
are already in flight.  Every step is a visit, so the ring starts and
waits one copy a step, and only its warm-up and its last ``depth``
refills stop at the visit count.  Both paths execute the identical
accumulation order, so results are bit-identical across depths
(test-enforced).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "VMEM_LIMIT_BYTES",
    "bsr_spmm_pallas",
    "bsr_gather_spmm_pallas",
    "frontier_round_bsr_pallas",
]

# Scoped-VMEM limit every kernel here compiles under; the tune model's
# feasibility check budgets against this same number.  bs=512, C=64,
# depth=4 needs about 5 MiB by that model.
VMEM_LIMIT_BYTES = 32 * 2**20
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _mxu_dot(a, b, out_dtype):
    # f32 tiles times f32 fluid at full f32 precision: a single bf16 MXU
    # pass would lose ~3 digits per push, far above the solver's |F|_1
    # certificate.
    return jnp.dot(a, b, preferred_element_type=out_dtype,
                   precision=jax.lax.Precision.HIGHEST)


def _kernel(block_row_ref, block_col_ref, blocks_ref, x_ref, o_ref):
    """One grid step: o[block_row[i]] += blocks[i] @ x[block_col[i]]."""
    i = pl.program_id(0)

    is_first = i == 0
    new_row = block_row_ref[i] != block_row_ref[jnp.maximum(i - 1, 0)]

    @pl.when(jnp.logical_or(is_first, new_row))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += _mxu_dot(blocks_ref[0], x_ref[0], o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_row_blocks", "interpret", "bs")
)
def bsr_spmm_pallas(
    blocks: jax.Array,  # [n_blocks, bs, bs]   dense tiles of P
    block_row: jax.Array,  # [n_blocks] int32, sorted ascending
    block_col: jax.Array,  # [n_blocks] int32
    x: jax.Array,  # [n_col_blocks, bs, C]  (sent fluid, tiled)
    n_row_blocks: int,
    *,
    bs: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """delta = P_bsr @ x, returns [n_row_blocks, bs, C].

    ``blocks[i]`` holds P[rows of block_row[i], cols of block_col[i]] with
    layout ``blocks[i][r, c] = P[block_row[i]*bs + r, block_col[i]*bs + c]``.

    Requires block_row sorted; empty block rows are fine (their output tile
    is zeroed by the epilogue wrapper in ops.py).
    """
    n_blocks = blocks.shape[0]
    c = x.shape[-1]
    out_shape = jax.ShapeDtypeStruct((n_row_blocks, bs, c), x.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_row, block_col
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda i, br, bc: (i, 0, 0)),
            pl.BlockSpec((1, bs, c), lambda i, br, bc: (bc[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, c), lambda i, br, bc: (br[i], 0, 0)),
    )
    fn = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )
    # output blocks never visited keep uninitialised garbage; mask them in
    # ops.py via the row-occupancy map (cheap [n_row_blocks] bool).
    return fn(block_row, block_col, blocks, x)


# --------------------------------------------------------------------------- #
# gather-indirection SpMM: tiles stay in a row-owned layout (the distributed
# engine permutes them with bucket moves); a per-round visit order — sorted by
# destination block — arrives through scalar prefetch, so the same revisiting-
# output accumulation works without ever materialising a gathered/sorted copy
# of the tile array in HBM.
# --------------------------------------------------------------------------- #
def _gather_kernel(visit_block_ref, visit_row_ref, visit_col_ref,
                   blocks_ref, x_ref, o_ref):
    """Step i: o[visit_row[i]] += blocks[visit_block[i]] @ x[visit_col[i]]."""
    i = pl.program_id(0)
    is_first = i == 0
    new_row = visit_row_ref[i] != visit_row_ref[jnp.maximum(i - 1, 0)]

    @pl.when(jnp.logical_or(is_first, new_row))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += _mxu_dot(blocks_ref[0], x_ref[0], o_ref.dtype)


def _gather_kernel_dma(visit_block_ref, visit_row_ref, visit_col_ref,
                       blocks_hbm_ref, x_ref, o_ref, buf_ref, sem_ref,
                       *, v: int, depth: int):
    """Manual-DMA twin of :func:`_gather_kernel` (``buffer_depth >= 2``).

    The tile pool stays in HBM; a ``[depth, bs, bs]`` VMEM ring holds the
    in-flight gathers.  Step ``i`` waits on slot ``i % depth``, multiplies,
    then immediately reuses the slot to start the copy for step
    ``i + depth`` — so up to ``depth`` tile gathers overlap the MXU work.
    The warm-up and the refill stop at the grid's length, the visit
    count, so every started copy is waited exactly once.
    """
    i = pl.program_id(0)
    n = pl.num_programs(0)
    is_first = i == 0
    new_row = visit_row_ref[i] != visit_row_ref[jnp.maximum(i - 1, 0)]

    def tile_dma(slot, step):
        return pltpu.make_async_copy(
            blocks_hbm_ref.at[visit_block_ref[step]],
            buf_ref.at[slot],
            sem_ref.at[slot],
        )

    @pl.when(is_first)
    def _warmup():
        for d in range(min(depth, v)):
            @pl.when(d < n)
            def _start(d=d):
                tile_dma(d, d).start()

    @pl.when(jnp.logical_or(is_first, new_row))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    slot = jax.lax.rem(i, jnp.int32(depth))
    tile_dma(slot, i).wait()
    o_ref[0] += _mxu_dot(buf_ref[slot], x_ref[0], o_ref.dtype)

    @pl.when(i + depth < n)
    def _prefetch():
        tile_dma(slot, i + depth).start()


@functools.partial(
    jax.jit,
    static_argnames=("n_row_blocks", "interpret", "bs", "buffer_depth"),
)
def bsr_gather_spmm_pallas(
    blocks: jax.Array,  # [n_tiles, bs, bs] row-owned tile pool (any order)
    visit_block: jax.Array,  # [V] int32 index into ``blocks``
    visit_row: jax.Array,  # [V] int32 destination block row of each visit
    visit_col: jax.Array,  # [V] int32 source block col of each visit
    x: jax.Array,  # [n_col_blocks, bs, C]
    n_row_blocks: int,
    *,
    bs: int,
    interpret: bool = False,
    buffer_depth: int = 1,
    n_visits: jax.Array | None = None,  # int32 scalar, traced
) -> jax.Array:
    """delta = sum_i blocks[visit_block[i]] @ x[visit_col[i]] into visit_row[i].

    The grid has one step per visit, ``i < n_visits``; with ``n_visits``
    None every one of the V entries is a visit.  The visits must be sorted
    by ``visit_row``.  Entries from ``n_visits`` on are never read as
    visits, but they must hold valid indices: a list without visits still
    runs one step, on entry 0, whose row the caller drops.  The visit
    arrays may be computed in-graph each round — scalar prefetch takes
    traced values.  Rows never visited keep uninitialised garbage; callers
    mask them with the rows the visits reach.

    ``buffer_depth`` selects the tile-fetch strategy: 1 = automatic BlockSpec
    pipelining, >= 2 = a manual ``depth``-deep async-copy ring (see module
    docstring).  Results are bit-identical across depths.
    """
    if buffer_depth < 1:
        raise ValueError(f"buffer_depth must be >= 1, got {buffer_depth}")
    v = visit_block.shape[0]
    c = x.shape[-1]
    out_shape = jax.ShapeDtypeStruct((n_row_blocks, bs, c), x.dtype)
    grid = (v,) if n_visits is None else (jnp.maximum(n_visits, 1),)
    if buffer_depth == 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # visit_block, visit_row, visit_col
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bs, bs), lambda i, vb, vr, vc: (vb[i], 0, 0)),
                pl.BlockSpec((1, bs, c), lambda i, vb, vr, vc: (vc[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, bs, c), lambda i, vb, vr, vc: (vr[i], 0, 0)
            ),
        )
        body = _gather_kernel
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # tile pool stays in HBM
                pl.BlockSpec((1, bs, c), lambda i, vb, vr, vc: (vc[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, bs, c), lambda i, vb, vr, vc: (vr[i], 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((buffer_depth, bs, bs), blocks.dtype),
                pltpu.SemaphoreType.DMA((buffer_depth,)),
            ],
        )
        body = functools.partial(_gather_kernel_dma, v=v, depth=buffer_depth)
    fn = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )
    return fn(visit_block, visit_row, visit_col, blocks, x)


# --------------------------------------------------------------------------- #
# fused frontier round: threshold masking of the sent fluid and the per-block-
# row residual reduction live inside the kernel, so one grid sweep over the
# round's visit list (the tiles of the armed block columns) turns F into
# F' = F - sent + P @ sent on every visited row and emits the per-row |F'|_1
# partial sums the solver's stopping rule needs.
# --------------------------------------------------------------------------- #
# A visit packs its tile's block row and block column into one int32,
# ``row << VISIT_ROW_SHIFT | col``, so the visit list takes no more scalar
# memory than the block_row/block_col pair it stands in for.
VISIT_ROW_SHIFT = 16
_VISIT_COL_MASK = (1 << VISIT_ROW_SHIFT) - 1
# the packed word is an int32: rows below 2**15, columns below 2**16
MAX_ROW_BLOCKS = 1 << (31 - VISIT_ROW_SHIFT)


def _visit_row(rc):
    return rc >> VISIT_ROW_SHIFT


def _visit_col(rc):
    return rc & _VISIT_COL_MASK


def _frontier_step(visit_rc_ref, col_active_ref, tile, f_col_ref,
                   wt_col_ref, f_row_ref, wt_row_ref, o_ref, l1_ref):
    """Grid step i, on visit i (visits sorted by block row):

    * first visit of a row: seed o with the row's kept fluid
      ``where(sel, 0, f)`` (the un-diffused residual) where
      ``sel = (|f| * wt > 1) & (col_active[row] != 0)`` — a node only counts
      as "sent" if its own block column is armed this round, so deferred
      columns (occupancy threshold) keep their fluid intact,
    * every visit: accumulate ``tile @ sent(col)`` where
      ``sent = where(|f| * wt > 1, f, 0)`` is recomputed in-register —
      ``wt = w / T`` folds the threshold into the weights so no scalar
      operand is needed; the visit list holds armed columns' tiles only,
    * last visit of a row: reduce ``|o|_1`` into the per-row residual output.

    ``tile()`` reads the step's tile from wherever the fetch strategy put it.
    """
    i = pl.program_id(0)
    n = pl.num_programs(0)
    row = _visit_row(visit_rc_ref[i])
    prev_row = _visit_row(visit_rc_ref[jnp.maximum(i - 1, 0)])
    next_row = _visit_row(visit_rc_ref[jnp.minimum(i + 1, n - 1)])
    first = jnp.logical_or(i == 0, row != prev_row)
    last = jnp.logical_or(i == n - 1, next_row != row)

    @pl.when(first)
    def _seed_kept_fluid():
        fr = f_row_ref[0]
        sel = jnp.logical_and(
            jnp.abs(fr) * wt_row_ref[0] > 1.0, col_active_ref[row] != 0
        )
        o_ref[0] = jnp.where(sel, jnp.zeros_like(fr), fr)

    fc = f_col_ref[0]
    sent = jnp.where(jnp.abs(fc) * wt_col_ref[0] > 1.0, fc, jnp.zeros_like(fc))
    o_ref[0] += _mxu_dot(tile(), sent, o_ref.dtype)

    @pl.when(last)
    def _row_residual():
        l1_ref[0] = jnp.sum(jnp.abs(o_ref[0])).reshape(1, 1)


def _frontier_kernel(visit_tile_ref, visit_rc_ref, col_active_ref,
                     blocks_ref, f_col_ref, wt_col_ref, f_row_ref, wt_row_ref,
                     o_ref, l1_ref):
    """BlockSpec-pipelined step (``buffer_depth == 1``): the automatic
    pipeline has already brought tile ``visit_tile[i]`` into VMEM."""
    _frontier_step(visit_rc_ref, col_active_ref, lambda: blocks_ref[0],
                   f_col_ref, wt_col_ref, f_row_ref, wt_row_ref, o_ref,
                   l1_ref)


def _frontier_kernel_dma(visit_tile_ref, visit_rc_ref, col_active_ref,
                         blocks_hbm_ref, f_col_ref, wt_col_ref, f_row_ref,
                         wt_row_ref, o_ref, l1_ref, buf_ref, sem_ref,
                         *, n_tiles: int, depth: int):
    """Manual-DMA twin of :func:`_frontier_kernel` (``buffer_depth >= 2``).

    Every step is a visit, so every step starts and waits one copy: step
    ``i`` waits on slot ``i % depth`` and refills it with the tile of visit
    ``i + depth``.  The warm-up and the refill stop at the round's visit
    count, so every started copy is waited exactly once.
    """
    i = pl.program_id(0)
    n = pl.num_programs(0)

    def tile_dma(slot, step):
        return pltpu.make_async_copy(
            blocks_hbm_ref.at[visit_tile_ref[step]], buf_ref.at[slot],
            sem_ref.at[slot])

    @pl.when(i == 0)
    def _warmup():
        for d in range(min(depth, n_tiles)):
            @pl.when(d < n)
            def _start(d=d):
                tile_dma(d, d).start()

    slot = jax.lax.rem(i, jnp.int32(depth))
    tile_dma(slot, i).wait()
    _frontier_step(visit_rc_ref, col_active_ref, lambda: buf_ref[slot],
                   f_col_ref, wt_col_ref, f_row_ref, wt_row_ref, o_ref,
                   l1_ref)

    @pl.when(i + depth < n)
    def _prefetch():
        tile_dma(slot, i + depth).start()


@functools.partial(
    jax.jit,
    static_argnames=("n_row_blocks", "interpret", "bs", "buffer_depth"),
)
def frontier_round_bsr_pallas(
    blocks: jax.Array,  # [n_tiles, bs, bs] dense tiles of P, row-sorted
    visit_tile: jax.Array,  # [n_tiles] int32, the round's visits first
    visit_rc: jax.Array,  # [n_tiles] int32, row << VISIT_ROW_SHIFT | col
    n_visits: jax.Array,  # int32 scalar, the visit count of the round
    col_active: jax.Array,  # [n_col_blocks] int32 occupancy of the frontier
    f: jax.Array,  # [n_col_blocks, bs, C] residual fluid, tiled
    wt: jax.Array,  # [n_col_blocks, bs, 1] selection weights / threshold
    n_row_blocks: int,
    *,
    bs: int = 128,
    interpret: bool = False,
    buffer_depth: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """One fused frontier round over the round's visit list.

    The grid has one step per visit: visit ``i`` multiplies tile
    ``visit_tile[i]``, of block row and column ``visit_rc[i]``, for
    ``i < n_visits``.  The visits are the tiles of the armed block columns
    in their row-sorted order (``ops.compact_visits`` builds them), so a
    round walks the armed tiles only and no step is spent on a column
    without sent fluid.  Entries from ``n_visits`` on are never read as
    visits; they must hold valid tile indices, because a round without
    visits still runs one step, whose output no row keeps.

    Returns ``(f_new, row_l1)`` with ``f_new: [n_row_blocks, bs, C]`` holding
    ``F - sent + P @ sent`` for every *visited* block row and
    ``row_l1: [n_row_blocks, 1, 1]`` its per-row |·|_1 (3-D so that its
    ``(1, 1, 1)`` block spans the array's last two dims, as Mosaic
    requires of a block that is not (8, 128)-aligned).  Rows without a
    visit are left uninitialised (garbage) in BOTH outputs by design — the
    ops.py wrapper substitutes the kept fluid ``F - sent`` there via the
    round's row-visited map.  The square tiling (n_col_blocks ==
    n_row_blocks) means the f/wt operands serve double duty: indexed by
    the visit's column for the sent gather and by its row for the
    kept-fluid seeding.

    ``buffer_depth`` selects the tile-fetch strategy: 1 = automatic BlockSpec
    pipelining, >= 2 = a manual ``depth``-deep async-copy ring over the
    visits (see module docstring).  Bit-identical across depths.
    """
    if buffer_depth < 1:
        raise ValueError(f"buffer_depth must be >= 1, got {buffer_depth}")
    if n_row_blocks > MAX_ROW_BLOCKS:
        raise ValueError(f"{n_row_blocks} block rows do not pack into a "
                         f"visit word (at most {MAX_ROW_BLOCKS})")
    n_tiles = blocks.shape[0]
    c = f.shape[-1]
    out_shape = (
        jax.ShapeDtypeStruct((n_row_blocks, bs, c), f.dtype),
        jax.ShapeDtypeStruct((n_row_blocks, 1, 1), f.dtype),
    )

    def by_col(i, vt, rc, ca):
        return _visit_col(rc[i]), 0, 0

    def by_row(i, vt, rc, ca):
        return _visit_row(rc[i]), 0, 0

    fluid_specs = [
        pl.BlockSpec((1, bs, c), by_col),
        pl.BlockSpec((1, bs, 1), by_col),
        pl.BlockSpec((1, bs, c), by_row),
        pl.BlockSpec((1, bs, 1), by_row),
    ]
    out_specs = (
        pl.BlockSpec((1, bs, c), by_row),
        pl.BlockSpec((1, 1, 1), by_row),
    )
    # a round without visits runs one step whose rows the wrapper drops
    grid = (jnp.maximum(n_visits, 1),)
    if buffer_depth == 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # visit_tile, visit_rc, col_active
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bs, bs), lambda i, vt, rc, ca: (vt[i], 0, 0)),
                *fluid_specs,
            ],
            out_specs=out_specs,
        )
        body = _frontier_kernel
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # tile pool stays in HBM
                *fluid_specs,
            ],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((buffer_depth, bs, bs), blocks.dtype),
                pltpu.SemaphoreType.DMA((buffer_depth,)),
            ],
        )
        body = functools.partial(
            _frontier_kernel_dma, n_tiles=n_tiles, depth=buffer_depth
        )
    fn = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )
    return fn(visit_tile, visit_rc, col_active, blocks, f, wt, f, wt)
