"""Jitted wrapper for decode attention: head grouping + backend selection."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref


@functools.partial(
    jax.jit,
    static_argnames=("softcap", "scale", "window", "block_k", "backend", "interpret"),
)
def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    layer: Optional[jax.Array] = None,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    block_k: int = 512,
    backend: str = "pallas",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One-token GQA decode over a KV cache.

    q: (B, Hq, D); k, v: one layer's cache (B, Hkv, S, D), or every
    layer's, stacked with the sequence minor (L, B, Hkv, D, S), with the
    scalar ``layer`` to read; lengths: (B,) → (B, Hq, D).
    """
    if (k.ndim == 5) != (layer is not None):
        raise ValueError("a stacked (L, B, Hkv, D, S) cache needs a layer, "
                         "and only a stacked cache takes one")
    b, hq, d = q.shape
    hkv = k.shape[-3]
    assert hq % hkv == 0
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    if backend == "ref":
        if layer is not None:
            k, v = (jnp.swapaxes(jax.lax.dynamic_index_in_dim(
                c, layer, 0, keepdims=False), 2, 3) for c in (k, v))
        out = decode_attention_ref(qg, k, v, lengths, softcap=softcap,
                                   scale=scale, window=window)
    else:
        out = decode_attention_pallas(qg, k, v, lengths, layer=layer,
                                      softcap=softcap, scale=scale,
                                      window=window, block_k=block_k,
                                      interpret=interpret)
    return out.reshape(b, hq, d)
