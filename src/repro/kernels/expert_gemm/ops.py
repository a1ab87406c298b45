"""Jitted public wrapper for the grouped expert GEMM: block choice, backend.

The kernel is the whole of this jitted function, so its custom call keeps
the name ``expert_gemm`` in a profile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.expert_gemm.kernel import expert_gemm_pallas
from repro.kernels.expert_gemm.ref import expert_gemm_ref

W_BLOCK_BYTES = 8 * 1024 * 1024   # largest (K, block_n) weight block


def block_n_for(k: int, n: int, itemsize: int) -> int:
    """The widest block of N (N itself, else a multiple of 128 dividing
    it) whose (K, block_n) weight block fits ``W_BLOCK_BYTES``."""
    if n % 128:
        return n
    best = 128
    for bn in range(128, n + 1, 128):
        if n % bn == 0 and k * bn * itemsize <= W_BLOCK_BYTES:
            best = bn
    return best


@functools.partial(jax.jit, static_argnames=("block_m", "out_dtype",
                                             "backend", "interpret"))
def expert_gemm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                block_m: int, out_dtype=None, backend: str = "pallas",
                interpret: Optional[bool] = None) -> jax.Array:
    """Grouped GEMM over expert-sorted rows.

    x: (R, K), rows sorted by group, group e's ``group_sizes[e]`` rows
    starting at row ``block_m * sum_{f<e} ceil(group_sizes[f] / block_m)``;
    w: (G, K, N) → (R, N). Rows outside every group are undefined.
    """
    if backend == "ref":
        return expert_gemm_ref(x, w, group_sizes, block_m=block_m,
                               out_dtype=out_dtype)
    bn = block_n_for(x.shape[1], w.shape[2], w.dtype.itemsize)
    return expert_gemm_pallas(x, w, group_sizes, block_m=block_m, block_n=bn,
                              out_dtype=out_dtype, interpret=interpret)
