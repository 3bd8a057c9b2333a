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
        _spec((nrb,), i32, one_chip),
        _spec((nrb, bs, c), jnp.float32, one_chip),
        _spec((nrb, bs, 1), jnp.float32, one_chip),
    )
    fn = functools.partial(frontier_round_bsr_pallas, n_row_blocks=nrb,
                           bs=bs, buffer_depth=depth)
    compiled = jax.jit(fn).lower(*args).compile()
    assert _hlo_has_kernel(compiled)


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_gather_kernel_compiles_for_v5e(one_chip, depth):
    s, r_total, n_tiles = 128, 64, 320
    i32 = jnp.int32
    args = (
        _spec((n_tiles, s, s), jnp.float32, one_chip),
        _spec((n_tiles,), i32, one_chip),
        _spec((n_tiles,), i32, one_chip),
        _spec((n_tiles,), i32, one_chip),
        _spec((r_total, s, 1), jnp.float32, one_chip),
    )
    fn = functools.partial(bsr_gather_spmm_pallas, n_row_blocks=r_total,
                           bs=s, buffer_depth=depth)
    compiled = jax.jit(fn).lower(*args).compile()
    assert _hlo_has_kernel(compiled)


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
