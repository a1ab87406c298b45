"""Multi-head Latent Attention (MiniCPM3 / DeepSeek-style MLA).

The query is projected directly (``q_lora_rank`` None, as DeepSeek-V2-Lite
and Moonlight) or through its own low-rank latent (MiniCPM3).

Train/prefill: latent KV is expanded to per-head K/V and the standard flash
kernel runs. Decode: the **latent cache** is the near-memory operand — we use
the absorbed-matmul identity

    score_h = q_nope_hᵀ W_uk_h c + q_rope_hᵀ k_rope
            = [W_uk_hᵀ q_nope_h ; q_rope_h] · [c ; k_rope]

so single-token decode is a cache-resident sweep over the *compressed* latent
stream (kv_lora_rank + rope_dim per token instead of 2·H·head_dim) — ARCANE's
"compute where the cache lives" with an 18× smaller cache for MiniCPM3's
geometry. The value path absorbs W_uv the same way.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.engine import ArcaneEngine
from repro.models.layers import (apply_rope, dense, dense_init, rmsnorm,
                                 rmsnorm_init, truncated_normal_init)


def mla_init(key, cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dt = cfg.pdtype
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    keys = jax.random.split(key, 8)
    if m.q_lora_rank is None:           # a direct q projection
        q = {"q": dense_init(keys[0], d, h * qk_head, dt)}
    else:
        q = {"q_down": dense_init(keys[0], d, m.q_lora_rank, dt),
             "q_norm": rmsnorm_init(m.q_lora_rank, dt),
             "q_up": dense_init(keys[1], m.q_lora_rank, h * qk_head, dt)}
    return {
        **q,
        "kv_down": dense_init(keys[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dt),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dt),
        "k_up": truncated_normal_init(
            keys[3], (h, m.kv_lora_rank, m.qk_nope_head_dim), dt,
            1.0 / math.sqrt(m.kv_lora_rank)),
        "v_up": truncated_normal_init(
            keys[4], (h, m.kv_lora_rank, m.v_head_dim), dt,
            1.0 / math.sqrt(m.kv_lora_rank)),
        "o": dense_init(keys[5], h * m.v_head_dim, d, dt),
    }


def _project_qkv(engine, params, cfg, x, positions):
    """Shared q/latent computation. Returns q_nope, q_rope, c_kv, k_rope."""
    m = cfg.mla
    h = cfg.n_heads
    b = x.shape[0]
    s = x.shape[1]
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    if "q" in params:
        q = dense(engine, params["q"], x)
    else:
        q_lat = rmsnorm(params["q_norm"], dense(engine, params["q_down"], x))
        q = dense(engine, params["q_up"], q_lat)
    q = q.reshape(b, s, h, qk_head)
    q = q.transpose(0, 2, 1, 3)                                   # (B,H,S,qk)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    kv = dense(engine, params["kv_down"], x)                      # (B,S,r+rope)
    c_kv = rmsnorm(params["kv_norm"], kv[..., : m.kv_lora_rank])
    k_rope = kv[..., m.kv_lora_rank:][:, None]                    # (B,1,S,rope)
    q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta)
    k_rope = apply_rope(k_rope, positions, theta=cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(engine: ArcaneEngine, params: dict, cfg: ModelConfig,
                x: jax.Array, positions: jax.Array) -> jax.Array:
    """Training forward: expand latents to per-head K/V, flash attention."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    q_nope, q_rope, c_kv, k_rope = _project_qkv(engine, params, cfg, x, positions)
    k_nope = jnp.einsum("bsr,hrd->bhsd", c_kv, params["k_up"])
    v = jnp.einsum("bsr,hrd->bhsd", c_kv, params["v_up"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, h, s, m.qk_rope_head_dim))],
        axis=-1)
    scale = 1.0 / math.sqrt(qk_head)
    # v head dim may differ from qk head dim — pad for the shared kernel.
    if m.v_head_dim < qk_head:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_head - m.v_head_dim)))
    out = engine.attention(q, k, v, causal=True, scale=scale)
    out = out[..., : m.v_head_dim]
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * m.v_head_dim)
    return dense(engine, params["o"], out)


def mla_prefill(engine, params, cfg, x, positions, cache_c, cache_kr):
    """Prefill: run forward and stash the *latent* stream into the cache."""
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _project_qkv(engine, params, cfg, x, positions)
    out = mla_forward(engine, params, cfg, x, positions)
    cache_c = jax.lax.dynamic_update_slice(
        cache_c, c_kv.astype(cache_c.dtype), (0, 0, 0))
    cache_kr = jax.lax.dynamic_update_slice(
        cache_kr, k_rope[:, 0].astype(cache_kr.dtype), (0, 0, 0))
    return out, cache_c, cache_kr


def mla_decode(engine: ArcaneEngine, params: dict, cfg: ModelConfig,
               x: jax.Array, position: jax.Array,
               cache_c: jax.Array, cache_kr: jax.Array):
    """Absorbed single-token decode over the latent cache.

    x: (B, d); cache_c: (B, S, r); cache_kr: (B, S, rope).
    """
    m = cfg.mla
    b, _ = x.shape
    h = cfg.n_heads
    r = m.kv_lora_rank
    rope = m.qk_rope_head_dim
    qk_head = m.qk_nope_head_dim + rope
    q_nope, q_rope, c_new, kr_new = _project_qkv(
        engine, params, cfg, x[:, None, :], position[:, None])
    # write the new latent row
    cache_c = jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, 0))
    )(cache_c, c_new.astype(cache_c.dtype), position)
    cache_kr = jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, 0))
    )(cache_kr, kr_new[:, 0].astype(cache_kr.dtype), position)

    # absorb W_uk into q: q_eff = W_ukᵀ q_nope  → (B, H, r)
    q_eff = jnp.einsum("bhd,hrd->bhr", q_nope[:, :, 0, :], params["k_up"])
    q_full = jnp.concatenate([q_eff, q_rope[:, :, 0, :]], axis=-1)  # (B,H,r+rope)
    keys = jnp.concatenate([cache_c, cache_kr], axis=-1)[:, None]   # (B,1,S,r+rope)
    vals = jnp.pad(cache_c, ((0, 0), (0, 0), (0, rope)))[:, None]   # pad to r+rope
    lengths = position + 1
    scale = 1.0 / math.sqrt(qk_head)
    out = engine.decode_attention(q_full, keys.astype(q_full.dtype),
                                  vals.astype(q_full.dtype), lengths,
                                  scale=scale)                      # (B,H,r+rope)
    out_lat = out[..., :r]                                          # (B,H,r)
    out_v = jnp.einsum("bhr,hrd->bhd", out_lat, params["v_up"])
    out_v = out_v.reshape(b, h * m.v_head_dim)
    return dense(engine, params["o"], out_v), cache_c, cache_kr
