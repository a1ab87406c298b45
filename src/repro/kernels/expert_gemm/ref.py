"""Pure-jnp oracle for the grouped expert GEMM."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import cdiv


def expert_gemm_ref(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                    block_m: int, out_dtype=None) -> jax.Array:
    """Each group's rows (starting on multiples of ``block_m``, padded up
    to one) times its expert's weights; rows past the last group are 0."""
    padded = cdiv(group_sizes.astype(jnp.int32), block_m) * block_m
    out = jax.lax.ragged_dot(x, w, padded, preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)
