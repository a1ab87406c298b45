"""GQA attention (train forward, prefill-with-cache, single-token decode).

Projections run through the ArcaneEngine xmk0 dispatch; score/AV compute goes
through the flash-attention "complex instruction" (prefill) or the
cache-resident decode kernel (serving) — the near-memory principle applied to
the KV cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.engine import ArcaneEngine
from repro.distributed.sharding import constrain
from repro.models.layers import apply_rope, dense, dense_init


def attention_init(key, cfg: ModelConfig, *, cross: bool = False) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = cfg.pdtype
    return {
        "q": dense_init(kq, d, cfg.n_heads * hd, dt, bias=cfg.qkv_bias),
        "k": dense_init(kk, d, cfg.n_kv_heads * hd, dt, bias=cfg.qkv_bias),
        "v": dense_init(kv, d, cfg.n_kv_heads * hd, dt, bias=cfg.qkv_bias),
        "o": dense_init(ko, cfg.n_heads * hd, d, dt),
    }


# ---------------------------------------------------------- cache layout
# Attention K/V caches, self and cross, keep the sequence on the last axis:
# (B, Hkv, hd, S) for one layer, (L, B, Hkv, hd, S) stacked. A TPU tiles an
# array's two minor dims by (8, 128): a (S, hd) cache with hd = 80 would pad
# hd to 128 lanes, so XLA keeps it transposed in HBM and every row-major
# read of a layer is a relayout copy; (hd, S) is dense as it stands, and the
# decode kernel reads a layer where it lies (kernels/decode_attention).
def kv_cache_shape(batch: int, n_kv: int, hd: int, s: int) -> tuple:
    return (batch, n_kv, hd, s)


def kv_cache_len(cache: jax.Array) -> int:
    """Positions a K/V cache holds, per layer or stacked."""
    return cache.shape[-1]


def to_cache_layout(x: jax.Array) -> jax.Array:
    """(B, Hkv, S, hd) heads as attention computes them → cache layout."""
    return jnp.swapaxes(x, -1, -2)


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    b, s, _ = x.shape
    out = x.reshape(b, s, n, -1).transpose(0, 2, 1, 3)   # (B, H, S, D)
    return constrain(out, "batch", "model", None, None)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def attention_forward(
    engine: ArcaneEngine,
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    window: Optional[int] = None,
    kv_override: Optional[tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
) -> jax.Array:
    """Training/prefill forward. x: (B, S, d). kv_override: cross-attention."""
    q = _split_heads(dense(engine, params["q"], x), cfg.n_heads)
    if kv_override is None:
        k = _split_heads(dense(engine, params["k"], x), cfg.n_kv_heads)
        v = _split_heads(dense(engine, params["v"], x), cfg.n_kv_heads)
        q = apply_rope(q, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
        k = apply_rope(k, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
    else:
        k, v = kv_override
    out = engine.attention(q, k, v, causal=causal, window=window,
                           softcap=cfg.attn_softcap)
    return dense(engine, params["o"], _merge_heads(out))


def attention_prefill(
    engine: ArcaneEngine,
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    *,
    window: Optional[int] = None,
    ring: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill: forward + write K/V into the cache at [0, S).

    cache_k, cache_v: (B, Hkv, hd, S_cache), sequence minor.

    Ring mode (window-sized cache for local layers, §Perf iteration 5): only
    the last ``window`` positions are kept, placed at slot ``pos % window``
    — a static permutation because S and window are static.
    """
    b, s, _ = x.shape
    q = _split_heads(dense(engine, params["q"], x), cfg.n_heads)
    k = _split_heads(dense(engine, params["k"], x), cfg.n_kv_heads)
    v = _split_heads(dense(engine, params["v"], x), cfg.n_kv_heads)
    q = apply_rope(q, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    k = apply_rope(k, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    # K and V go two ways, into flash attention and transposed into the
    # cache; computed once here, else XLA computes the rotation in both,
    # and where the flash tile does not divide the prompt the compiled
    # prefill then kept the FFN activations out of VMEM (1920 tokens,
    # stablelm-3b on a v5e: 602.8 ms a prefill, 535.0 with the barrier)
    k, v = jax.lax.optimization_barrier((k, v))
    out = engine.attention(q, k, v, causal=True, window=window,
                           softcap=cfg.attn_softcap)
    kt = to_cache_layout(k).astype(cache_k.dtype)
    vt = to_cache_layout(v).astype(cache_v.dtype)
    if ring:
        w = kv_cache_len(cache_k)
        keep = min(w, s)
        pos_tail = jnp.arange(s - keep, s)
        slots = pos_tail % w                      # static permutation
        cache_k = cache_k.at[..., slots].set(kt[..., s - keep:])
        cache_v = cache_v.at[..., slots].set(vt[..., s - keep:])
    else:
        cache_k = jax.lax.dynamic_update_slice(cache_k, kt, (0, 0, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, vt, (0, 0, 0, 0))
    return dense(engine, params["o"], _merge_heads(out)), cache_k, cache_v


def attention_decode(
    engine: ArcaneEngine,
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    position: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    layer: jax.Array,
    *,
    window: Optional[int] = None,
    ring: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x: (B, d); position: (B,) current index.

    cache_k, cache_v: every layer's cache, stacked (L, B, Hkv, hd, S). The
    new K/V columns are written in place at ``layer``, one per sequence, then
    the decode kernel reads that layer straight from the stack: no op
    outside the kernel touches a whole layer's cache. Sliding-window layers
    bound the sweep length via the kv length argument (cache is
    ring-buffered by the serving layer).
    """
    b, d = x.shape
    hd = cfg.resolved_head_dim
    q = dense(engine, params["q"], x[:, None, :])           # (B,1,Hq*hd)
    k = dense(engine, params["k"], x[:, None, :])
    v = dense(engine, params["v"], x[:, None, :])
    q = _split_heads(q, cfg.n_heads)                         # (B,Hq,1,hd)
    k = _split_heads(k, cfg.n_kv_heads)
    q = apply_rope(q, position[:, None], theta=cfg.rope_theta,
                   fraction=cfg.rope_fraction)
    k = apply_rope(k, position[:, None], theta=cfg.rope_theta,
                   fraction=cfg.rope_fraction)
    v = _split_heads(v, cfg.n_kv_heads)

    # write the new columns at per-sequence positions (ring: pos % window —
    # the ring holds exactly the window, so no extra masking is needed and
    # the softmax is order-independent)
    w = kv_cache_len(cache_k)
    slot = position % w if ring else position
    cache_k, cache_v = engine.kv_write(
        cache_k, cache_v, to_cache_layout(k), to_cache_layout(v),
        slot, layer)                                     # new: (B,Hkv,hd,1)
    lengths = jnp.minimum(position + 1, w) if ring else position + 1
    out = engine.decode_attention(q[:, :, 0, :], cache_k, cache_v, lengths,
                                  layer=layer, softcap=cfg.attn_softcap,
                                  window=None if ring else window)  # (B,Hq,hd)
    out = dense(engine, params["o"], out.reshape(b, cfg.n_heads * hd))
    return out, cache_k, cache_v
