"""Grouped expert GEMM Pallas kernel: each row tile times its expert's weights.

The rows of ``x`` are token rows sorted by expert, each expert's group
starting on a multiple of ``block_m`` rows and padded up to one (the MoE
layer lays them out so). The group sizes are scalar-prefetched into SMEM;
the index maps walk them to find the expert of each row tile, so the grid
visits the tiles in order and the weight block changes only where the
expert does. An expert that received no rows has no tile, and its weights
are never read; tiles past the last group compute nothing and map every
input to the last tile in use, so they move no data either.

Grid: (N blocks, row tiles), the row tiles inner, so an expert's weight
block is fetched once for all its tiles. K is whole in each block: the
expert widths of today's MoE models (d_model, expert width a few thousand)
fit a (K, block_n) weight block in VMEM, and one dot per step keeps the MXU
fed from a single DMA. Rows of tiles past the last group are left
undefined; the caller reads only the rows of its groups.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default

VMEM_LIMIT = 64 * 1024 * 1024    # of a v5e core's 128 MiB


def _tiles(sizes_ref, n_groups: int, bm: int):
    """Row tiles in use: every group's rows, rounded up to whole tiles."""
    used = jnp.int32(0)
    for e in range(n_groups):
        used = used + (sizes_ref[e] + bm - 1) // bm
    return used


def _tile_and_group(i, sizes_ref, n_groups: int, bm: int):
    """(tile, expert) that step ``i`` reads: past the last group, the last
    tile in use, so the blocks stay where they are and nothing is fetched."""
    tile = jnp.maximum(jnp.minimum(i, _tiles(sizes_ref, n_groups, bm) - 1), 0)
    end, group = jnp.int32(0), jnp.int32(0)
    for e in range(n_groups):
        end = end + (sizes_ref[e] + bm - 1) // bm
        group = group + (end <= tile).astype(jnp.int32)
    return tile, jnp.minimum(group, n_groups - 1)


def _kernel(sizes_ref, x_ref, w_ref, o_ref, *, n_groups: int, bm: int):
    @pl.when(pl.program_id(1) < _tiles(sizes_ref, n_groups, bm))
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def expert_gemm_pallas(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                       block_m: int, block_n: int, out_dtype=None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """x: (R, K), R a multiple of ``block_m``; w: (G, K, N), N a multiple
    of ``block_n``; group_sizes: (G,) int32 → (R, N)."""
    if interpret is None:
        interpret = interpret_default()
    r, k = x.shape
    g, _, n = w.shape
    assert r % block_m == 0 and n % block_n == 0, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    at = functools.partial(_tile_and_group, n_groups=g, bm=block_m)

    def x_map(j, i, sizes):
        return at(i, sizes)[0], 0

    def w_map(j, i, sizes):
        return at(i, sizes)[1], 0, j

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_n, r // block_m),
        in_specs=[pl.BlockSpec((block_m, k), x_map),
                  pl.BlockSpec((None, k, block_n), w_map)],
        out_specs=pl.BlockSpec((block_m, block_n), lambda j, i, sizes: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_groups=g, bm=block_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(group_sizes.astype(jnp.int32), x, w)
