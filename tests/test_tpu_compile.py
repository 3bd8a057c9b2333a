"""Compile the diffusion kernels for a described TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described.  That catches what interpret mode
cannot — blocks Mosaic refuses to tile, more VMEM than a kernel may use,
a program that does not fit the device's 16 GB — at the widths the solver
runs: bs = S = 128 tiles, C in {1, 8, 64} lanes, DMA depth in {1, 2, 4}.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every pytest worker
imports this file.  The persistent compile cache is turned off around
these compiles, since a compile for a described chip is written there
but cannot be read back without one.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.diffusion import (
    BsrMatrix,
    bsr_gather_spmm_pallas,
    frontier_round_bsr,
    frontier_round_bsr_pallas,
)

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo_has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("c", [1, 8, 64])
def test_frontier_kernel_compiles_for_v5e(one_chip, c, depth):
    bs, nrb, n_blocks = 128, 64, 320
    i32 = jnp.int32
    args = (
        _spec((n_blocks, bs, bs), jnp.float32, one_chip),
        _spec((n_blocks,), i32, one_chip),
        _spec((n_blocks,), i32, one_chip),
        _spec((), i32, one_chip),
        _spec((nrb,), i32, one_chip),
        _spec((nrb, bs, c), jnp.float32, one_chip),
        _spec((nrb, bs, 1), jnp.float32, one_chip),
    )
    fn = functools.partial(frontier_round_bsr_pallas, n_row_blocks=nrb,
                           bs=bs, buffer_depth=depth)
    compiled = jax.jit(fn).lower(*args).compile()
    assert _hlo_has_kernel(compiled)


@pytest.mark.parametrize("traced_grid", [False, True],
                         ids=["every_entry", "n_visits"])
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_gather_kernel_compiles_for_v5e(one_chip, depth, traced_grid):
    s, r_total, n_tiles = 128, 64, 320
    i32 = jnp.int32
    args = (
        _spec((n_tiles, s, s), jnp.float32, one_chip),
        _spec((n_tiles,), i32, one_chip),
        _spec((n_tiles,), i32, one_chip),
        _spec((n_tiles,), i32, one_chip),
        _spec((r_total, s, 1), jnp.float32, one_chip),
    )
    if traced_grid:
        def fn(*a, n_visits):
            return bsr_gather_spmm_pallas(
                *a, n_row_blocks=r_total, bs=s, buffer_depth=depth,
                n_visits=n_visits)

        lowered = jax.jit(fn).lower(*args, n_visits=_spec((), i32, one_chip))
    else:
        lowered = jax.jit(functools.partial(
            bsr_gather_spmm_pallas, n_row_blocks=r_total, bs=s,
            buffer_depth=depth)).lower(*args)
    assert _hlo_has_kernel(lowered.compile())


def test_frontier_round_at_2_21_fits_one_v5e(one_chip):
    """One whole jitted round (masking, kernel, epilogue) at N = 2^21.

    The tile count is the generator's bound: host_block_graph with
    span_hosts=2 puts at most 5 tiles in a block column, and N = 2^21
    has 16384 of them (the real graph has about 81.7k tiles).
    """
    bs, n = 128, 2**21
    nrb = n // bs
    n_blocks = 5 * nrb
    m = BsrMatrix.from_arrays(
        _spec((n_blocks, bs, bs), jnp.float32, one_chip),
        _spec((n_blocks,), jnp.int32, one_chip),
        _spec((n_blocks,), jnp.int32, one_chip),
        _spec((nrb,), jnp.bool_, one_chip),
        nrb, bs)
    f = _spec((n,), jnp.float32, one_chip)
    w = _spec((n,), jnp.float32, one_chip)
    t = _spec((), jnp.float32, one_chip)
    fn = functools.partial(frontier_round_bsr, backend="pallas",
                           interpret=False)
    compiled = jax.jit(fn).lower(m, f, w, t).compile()
    assert _hlo_has_kernel(compiled)
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < V5E_HBM_BYTES, (
        f"arguments {mem.argument_size_in_bytes} + temporaries "
        f"{mem.temp_size_in_bytes} bytes exceed one v5e")


@pytest.mark.parametrize("n", [2**21, 2_883_584])
def test_frontier_grain_at_2_21_fits_one_v5e(one_chip, n):
    """The session's whole jitted grain at N = 2^21, and at 2,883,584
    pages, the most that compiles while the kernel's scalar-prefetched
    lists fit in SMEM: the loop of fused rounds with its state, exact
    counters included.  The kernel keeps the name the benchmark's kernel
    reader matches, and each stage of a round carries its ``frontier.*``
    named scope in the op metadata."""
    from repro.api.session import _bsr_advance

    bs = 128
    nrb = n // bs
    n_blocks = 5 * nrb
    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32

    def spec(shape, dtype):
        return _spec(shape, dtype, one_chip)

    m = BsrMatrix.from_arrays(
        spec((n_blocks, bs, bs), f32), spec((n_blocks,), i32),
        spec((n_blocks,), i32), spec((nrb,), jnp.bool_), nrb, bs)
    state = (spec((n,), f32), spec((), f32), spec((n,), f32), spec((), f32),
             spec((2,), u32), spec((), i32), spec((2,), u32))
    compiled = _bsr_advance().lower(
        state, m, spec((n,), f32), spec((n,), i32), spec((n,), jnp.bool_),
        spec((nrb,), i32), spec((), f32), spec((), i32), gamma=1.2,
        backend="pallas", interpret=False, buffer_depth=1,
        occupancy_threshold=0.0).compile()
    text = compiled.as_text()
    assert "%frontier_round_bsr_pallas" in text
    for stage in ("select", "compact", "kernel", "kept", "residual",
                  "history", "count"):
        assert f"/frontier.{stage}/" in text, stage
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < V5E_HBM_BYTES


def test_engine_chunk_at_2_21_fits_one_v5e(one_chip, monkeypatch):
    """The k = 1 ``engine:bsr`` chunk at the shapes of the popsorted 2^21
    crawl: R = 16,386 bucket rows of S = 128 (2^21 / 128 real buckets and
    2 of headroom), T = 5 tiles a row, E = 8,958 edge slots a row (the
    largest bucket's links).  The gather kernel is in the program, it keeps
    the name the benchmark's reader matches, its grid's length is a traced
    operand (the round's armed visits) beside the same three prefetched
    lists of R·T slots, the chunk's stages carry their ``engine.*`` named
    scopes, and its arguments and temporaries fit one v5e."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import distributed as D

    r, s, t, e = 16_386, 128, 5, 8_958
    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    mesh = Mesh(np.array(list(one_chip.device_set)), ("pid",))
    row, rep = NamedSharding(mesh, P("pid")), NamedSharding(mesh, P())

    def spec(shape, dtype, sharding=row):
        return _spec(shape, dtype, sharding)

    cfg = D.EngineConfig(k=1, target_error=2.0**-21, eps=0.15,
                         buckets_per_dev=r, headroom=2,
                         diffusion_backend="bsr")
    # shape carriers: the chunk reads only the layout's sizes
    arrays = D.EngineArrays(
        f0=np.zeros((r, s), np.float32), w=None, src_slot=None,
        dst_bucket=None, dst_slot=None, wgt=np.zeros((1, e)),
        pos_of_bucket=None, node_of_slot=None, n=2**21, n_edges=0,
        tiles=np.zeros((1,)), tile_dst=None, slot_out_deg=None)
    # the chunk picks the Mosaic kernel where JAX's backend is a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(D, "_CHUNK_CACHE", {})
    engine = D.DistributedEngine(arrays, cfg, mesh=mesh)
    state = D.EngineState(
        f=spec((r, s), f32), h=spec((r, s), f32),
        outbox=spec((1, r * s), f32), t=spec((1,), f32),
        pos_of_bucket=spec((r,), i32, rep), ops=spec((1, 2), u32),
        rounds=spec((), i32, rep), armed=spec((1, 2), u32))
    operands = (spec((r, s), f32), spec((r, e), i32), spec((r, e), i32),
                spec((r, e), i32), spec((r, e), f32),
                spec((r, t, s, s), f32), spec((r, t), i32),
                spec((r, s), i32))
    compiled = engine._chunk.lower(state, *operands).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%bsr_gather_spmm_pallas" in text
    kernels = re.findall(r"%bsr_gather_spmm_pallas[.\d]* = .*custom-call\(.*",
                         text)
    assert kernels
    slots = f"s32[{r * t}]{{0}}"
    for line in kernels:
        assert (f"operand_layout_constraints={{s32[], {slots}, {slots}, "
                f"{slots}, f32[{r * t},") in line, line[:300]
    for stage in ("select", "push", "exchange", "residual"):
        assert f"/engine.{stage}/" in text, stage
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < V5E_HBM_BYTES, (
        f"arguments {mem.argument_size_in_bytes} + temporaries "
        f"{mem.temp_size_in_bytes} bytes exceed one v5e")
