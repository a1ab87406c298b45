"""Write one decode step's K/V columns into the stacked cache, in place.

cache_k, cache_v: (L, B, Hkv, D, S), the model's K/V cache (sequence minor,
see ``kernels/decode_attention``); new_k, new_v: (B, Hkv, D, 1), the step's
columns; slot: (B,) int32, the position each sequence writes; layer: the
layer. Both caches are aliased to the outputs, so only the blocks written
move: for each sequence one (Hkv, D, lanes) block around its slot is read,
the column set, and the block written back. ``lanes`` is 128, a TPU's lane
width, or all of S when it is shorter; where 128 does not divide S the last
block runs past the cache's end, and only what lies inside is read and
written.

One call per layer replaces a dynamic-update-slice per sequence and per
cache, each a separate small op on the device.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default


def _write_kernel(slot_ref, layer_ref, nk_ref, nv_ref, k_ref, v_ref,
                  ko_ref, vo_ref, *, lanes: int):
    col = slot_ref[pl.program_id(0)] % lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, k_ref.ndim - 1)
    ko_ref[...] = jnp.where(lane == col, nk_ref[...], k_ref[...])
    vo_ref[...] = jnp.where(lane == col, nv_ref[...], v_ref[...])


def kv_write_pallas(cache_k, cache_v, new_k, new_v, slot, layer, *,
                    interpret: Optional[bool] = None):
    if interpret is None:
        interpret = interpret_default()
    _, b, h, d, s = cache_k.shape
    lanes = min(128, s)
    new_spec = pl.BlockSpec((1, h, d, 1), lambda bb, sl, ly: (bb, 0, 0, 0))
    cache_spec = pl.BlockSpec(
        (None, 1, h, d, lanes),
        lambda bb, sl, ly: (ly[0], bb, 0, 0, sl[bb] // lanes))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[new_spec, new_spec, cache_spec, cache_spec],
        out_specs=[cache_spec, cache_spec],
    )
    return pl.pallas_call(
        functools.partial(_write_kernel, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
                   jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype)),
        # operands count the two scalar-prefetch arguments first
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(slot.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      new_k.astype(cache_k.dtype), new_v.astype(cache_v.dtype),
      cache_k, cache_v)
