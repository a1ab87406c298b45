"""Pure-jnp oracle for the K/V column write: one scatter per cache."""
from __future__ import annotations

import jax.numpy as jnp


def kv_write_ref(cache_k, cache_v, new_k, new_v, slot, layer):
    """cache_*: (L, B, Hkv, D, S); new_*: (B, Hkv, D, 1); slot: (B,)."""
    rows = jnp.arange(cache_k.shape[1])

    def put(cache, new):
        return cache.at[layer, rows, :, :, slot].set(
            new[..., 0].astype(cache.dtype))
    return put(cache_k, new_k), put(cache_v, new_v)
