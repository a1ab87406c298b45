"""Jitted wrapper for the K/V column write: backend selection."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.kv_write.kernel import kv_write_pallas
from repro.kernels.kv_write.ref import kv_write_ref


@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def kv_write(
    cache_k: jax.Array,
    cache_v: jax.Array,
    new_k: jax.Array,
    new_v: jax.Array,
    slot: jax.Array,
    layer: jax.Array,
    *,
    backend: str = "pallas",
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Put each sequence's new K and V column at ``slot`` of layer
    ``layer``. cache_*: (L, B, Hkv, D, S); new_*: (B, Hkv, D, 1); slot:
    (B,) → the two caches, updated in place where the caller donates them.
    """
    if backend == "ref":
        return kv_write_ref(cache_k, cache_v, new_k, new_v, slot, layer)
    return kv_write_pallas(cache_k, cache_v, new_k, new_v, slot, layer,
                           interpret=interpret)
