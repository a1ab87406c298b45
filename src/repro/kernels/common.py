"""Shared helpers for the Pallas kernel suite.

All kernels target TPU (pl.pallas_call + explicit BlockSpec VMEM tiling).
``interpret_default()`` runs them in interpret mode when JAX's backend is not
a TPU, which is how the CPU tests validate them; on a TPU they always lower
to Mosaic. Per-chip peaks live in ``repro.peaks``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.cache
def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pad_to(x: jax.Array, shape: tuple[int, ...], value=0) -> jax.Array:
    """Zero-pad trailing edges of ``x`` up to ``shape``."""
    pads = [(0, t - s) for s, t in zip(x.shape, shape)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads, constant_values=value)


def acc_dtype(dtype) -> jnp.dtype:
    """Accumulator type: int32 for integer datapaths, f32 otherwise (MXU)."""
    return jnp.int32 if jnp.issubdtype(jnp.dtype(dtype), jnp.integer) else jnp.float32


NEG_INF = float(-1e30)   # mask value that survives bf16 rounding
