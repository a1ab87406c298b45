"""Layer blocks: norm/residual wiring around the sequence mixers + FFN/MoE.

A block is one position in the config's repeating layer pattern. Three entry
points per block — forward (train), prefill (cache write), decode (one token,
cache read/update) — each dispatching on LayerSpec.kind. The per-kind cache
pytrees are defined here so the serving layer and the launcher agree on
shapes.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.core.engine import ArcaneEngine
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import mla as mla_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.layers import make_norm
from repro.models.mlp import mlp, mlp_init
from repro.models.moe import moe, moe_init


def _norm(cfg):
    return make_norm(cfg.norm)


def block_init(key, cfg: ModelConfig, spec: LayerSpec, *,
               cross: bool = False) -> dict:
    ninit, _ = _norm(cfg)
    d = cfg.d_model
    dt = cfg.pdtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": ninit(d, dt)}
    if spec.kind in ("attn", "attn_local"):
        p["attn"] = attn.attention_init(k1, cfg)
    elif spec.kind == "mla":
        p["attn"] = mla_mod.mla_init(k1, cfg)
    elif spec.kind == "mamba":
        p["mixer"] = mam.mamba_init(k1, cfg)
    elif spec.kind == "rwkv":
        p["mixer"] = rwkv_mod.rwkv_init(k1, cfg)
        p["ln2"] = ninit(d, dt)
        return p  # rwkv carries its own channel-mix FFN
    else:
        raise ValueError(spec.kind)
    if cross:
        p["cross_ln"] = ninit(d, dt)
        p["cross"] = attn.attention_init(k4, cfg)
    p["ln2"] = ninit(d, dt)
    p["ffn"] = moe_init(k2, cfg) if spec.moe else mlp_init(k3, cfg)
    return p


def _ffn_apply(engine, params, cfg, spec, x, *, dropless: bool = False):
    """The FFN and its aux output; an MoE one dispatches dropless when
    serving (prefill and decode; aux: the rows each held expert received)
    and with capacity in the training forward (aux: the balance loss;
    ``models.moe``)."""
    if spec.moe:
        return moe(engine, params["ffn"], cfg, x, dropless=dropless)
    return mlp(engine, params["ffn"], cfg, x), jnp.float32(0.0)


def block_forward(engine: ArcaneEngine, params: dict, cfg: ModelConfig,
                  spec: LayerSpec, x: jax.Array, positions: jax.Array, *,
                  causal: bool = True,
                  enc_out: Optional[jax.Array] = None) -> tuple[jax.Array, jax.Array]:
    """Returns (x, moe_aux_loss)."""
    _, napply = _norm(cfg)
    x = constrain(x, "batch", None, None)
    h = napply(params["ln1"], x)
    if spec.kind in ("attn", "attn_local"):
        window = cfg.local_window if spec.kind == "attn_local" else None
        h = attn.attention_forward(engine, params["attn"], cfg, h, positions,
                                   window=window, causal=causal)
    elif spec.kind == "mla":
        h = mla_mod.mla_forward(engine, params["attn"], cfg, h, positions)
    elif spec.kind == "mamba":
        h, _ = mam.mamba_forward(engine, params["mixer"], cfg, h)
    elif spec.kind == "rwkv":
        h, _, _ = rwkv_mod.rwkv_time_mix(engine, params["mixer"], cfg, h)
        x = x + h
        h2 = napply(params["ln2"], x)
        cm, _ = rwkv_mod.rwkv_channel_mix(engine, params["mixer"], cfg, h2)
        return x + cm, jnp.float32(0.0)
    x = x + h
    if enc_out is not None and "cross" in params:
        hc = napply(params["cross_ln"], x)
        kx = attn._split_heads(
            attn.dense(engine, params["cross"]["k"], enc_out), cfg.n_kv_heads)
        vx = attn._split_heads(
            attn.dense(engine, params["cross"]["v"], enc_out), cfg.n_kv_heads)
        x = x + attn.attention_forward(
            engine, params["cross"], cfg, hc, positions, causal=False,
            kv_override=(kx, vx))
    h = napply(params["ln2"], x)
    h, aux = _ffn_apply(engine, params, cfg, spec, h)
    return x + h, aux


# ---------------------------------------------------------------- caches
def init_block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, dtype, *, cross_len: int = 0) -> dict:
    hd = cfg.resolved_head_dim
    if spec.kind in ("attn", "attn_local"):
        s_len = max_len
        if (spec.kind == "attn_local" and cfg.ring_local_cache
                and cfg.local_window and cfg.local_window < max_len):
            s_len = cfg.local_window          # ring buffer (§Perf iter. 5)
        shape = attn.kv_cache_shape(batch, cfg.n_kv_heads, hd, s_len)
        c = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if cross_len:
            xshape = attn.kv_cache_shape(batch, cfg.n_kv_heads, hd, cross_len)
            c["xk"] = jnp.zeros(xshape, dtype)
            c["xv"] = jnp.zeros(xshape, dtype)
        return c
    if spec.kind == "mla":
        m = cfg.mla
        return {"c": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
                "kr": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}
    if spec.kind == "mamba":
        di = cfg.mamba.expand * cfg.d_model
        return {"conv": jnp.zeros((batch, cfg.mamba.d_conv - 1, di),
                                  jnp.float32),
                "ssm": jnp.zeros((batch, di, cfg.mamba.d_state), jnp.float32)}
    if spec.kind == "rwkv":
        n = cfg.rwkv.head_size
        h = cfg.d_model // n
        return {"S": jnp.zeros((batch, h, n, n), jnp.float32),
                "tm_x": jnp.zeros((batch, cfg.d_model), dtype),
                "cm_x": jnp.zeros((batch, cfg.d_model), dtype)}
    raise ValueError(spec.kind)


def block_prefill(engine, params, cfg, spec, x, positions, cache, *,
                  enc_out=None, expert_rows: bool = False):
    """Prefill from position 0; returns (x, cache), and with
    ``expert_rows`` (x, cache, rows): an MoE block's rows per held expert
    (n_held,) int32, None for any other block."""
    _, napply = _norm(cfg)
    h = napply(params["ln1"], x)
    if spec.kind in ("attn", "attn_local"):
        window = cfg.local_window if spec.kind == "attn_local" else None
        ring = (window is not None and cfg.ring_local_cache
                and attn.kv_cache_len(cache["k"]) == window)
        h, cache["k"], cache["v"] = attn.attention_prefill(
            engine, params["attn"], cfg, h, positions, cache["k"], cache["v"],
            window=window, ring=ring)
    elif spec.kind == "mla":
        h, cache["c"], cache["kr"] = mla_mod.mla_prefill(
            engine, params["attn"], cfg, h, positions, cache["c"], cache["kr"])
    elif spec.kind == "mamba":
        # prefill == forward, carrying the final state into the cache
        b, s, _ = h.shape
        xz = None
        h, last = mam.mamba_forward(engine, params["mixer"], cfg, h)
        cache["ssm"] = last
        # conv state: last K-1 pre-conv activations — recompute cheaply
        # (the in_proj of the last K-1 tokens)
        from repro.models.layers import dense as _dense
        tail = napply(params["ln1"], x[:, -(cfg.mamba.d_conv - 1):])
        xz_tail = _dense(engine, params["mixer"]["in_proj"], tail)
        xi_tail = jnp.split(xz_tail, 2, axis=-1)[0]
        cache["conv"] = xi_tail.astype(jnp.float32)
    elif spec.kind == "rwkv":
        h, cache["S"], cache["tm_x"] = rwkv_mod.rwkv_time_mix(
            engine, params["mixer"], cfg, h)
        x = x + h
        h2 = napply(params["ln2"], x)
        cm, cache["cm_x"] = rwkv_mod.rwkv_channel_mix(
            engine, params["mixer"], cfg, h2)
        return (x + cm, cache, None) if expert_rows else (x + cm, cache)
    x = x + h
    if enc_out is not None and "cross" in params:
        hc = napply(params["cross_ln"], x)
        # compute & cache the cross K/V once
        kx = attn._split_heads(
            attn.dense(engine, params["cross"]["k"], enc_out), cfg.n_kv_heads)
        vx = attn._split_heads(
            attn.dense(engine, params["cross"]["v"], enc_out), cfg.n_kv_heads)
        cache["xk"] = attn.to_cache_layout(kx).astype(cache["xk"].dtype)
        cache["xv"] = attn.to_cache_layout(vx).astype(cache["xv"].dtype)
        x = x + attn.attention_forward(
            engine, params["cross"], cfg, hc, positions, causal=False,
            kv_override=(kx, vx))
    h = napply(params["ln2"], x)
    h, rows = _ffn_apply(engine, params, cfg, spec, h, dropless=True)
    if expert_rows:
        return x + h, cache, rows if spec.moe else None
    return x + h, cache


def decode_inplace_leaves(spec: LayerSpec) -> frozenset:
    """The cache leaves that the decode step updates in place in the stack.

    Self-attention (``attn``, ``attn_local``, ring or not) adds one K and
    one V column per sequence, so it writes them straight into the
    (n_periods, B, Hkv, hd, S) stack, and the decode kernel reads its layer
    from there. Every other leaf (MLA's latent rows, mamba and rwkv state,
    the read-only cross-attention K/V) is taken out per layer and put back.
    """
    if spec.kind in ("attn", "attn_local"):
        return frozenset(("k", "v"))
    return frozenset()


def block_decode(engine, params, cfg, spec, x, position, cache, layer, *,
                 enc_len: Optional[int] = None, expert_rows: bool = False):
    """One-token step. x: (B, d); cache: this pattern position's leaves for
    every period, stacked (n_periods, ...); layer: the period to run.
    Returns (x, cache) with the stack updated at ``layer``; with
    ``expert_rows`` (x, cache, rows) as ``block_prefill``."""
    inplace = decode_inplace_leaves(spec)
    one = {n: c if n in inplace
           else jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
           for n, c in cache.items()}
    x, new, rows = _block_decode_layer(engine, params, cfg, spec, x,
                                       position, dict(one), layer,
                                       enc_len=enc_len)
    out = {}
    for n, c in cache.items():
        if n in inplace:
            out[n] = new[n]
        elif new[n] is one[n]:          # read only: nothing to put back
            out[n] = c
        else:
            out[n] = jax.lax.dynamic_update_index_in_dim(c, new[n], layer, 0)
    if expert_rows:
        return x, out, rows if spec.moe else None
    return x, out


def _block_decode_layer(engine, params, cfg, spec, x, position, cache, layer,
                        *, enc_len: Optional[int] = None):
    """One layer's decode; the in-place leaves are still the whole stack.
    Returns (x, cache, the FFN's aux output)."""
    _, napply = _norm(cfg)
    h = napply(params["ln1"], x)
    if spec.kind in ("attn", "attn_local"):
        window = cfg.local_window if spec.kind == "attn_local" else None
        ring = (window is not None and cfg.ring_local_cache
                and attn.kv_cache_len(cache["k"]) == window)
        h, cache["k"], cache["v"] = attn.attention_decode(
            engine, params["attn"], cfg, h, position, cache["k"], cache["v"],
            layer, window=window, ring=ring)
    elif spec.kind == "mla":
        h, cache["c"], cache["kr"] = mla_mod.mla_decode(
            engine, params["attn"], cfg, h, position, cache["c"], cache["kr"])
    elif spec.kind == "mamba":
        h, cache["conv"], cache["ssm"] = mam.mamba_decode(
            engine, params["mixer"], cfg, h, cache["conv"], cache["ssm"])
    elif spec.kind == "rwkv":
        h, cache["S"], cache["tm_x"] = rwkv_mod.rwkv_time_mix_decode(
            engine, params["mixer"], cfg, h, cache["S"], cache["tm_x"])
        x = x + h
        h2 = napply(params["ln2"], x)
        cm, cache["cm_x"] = rwkv_mod.rwkv_channel_mix(
            engine, params["mixer"], cfg, h2[:, None, :],
            cache["cm_x"])
        return x + cm[:, 0], cache, None
    x = x + h
    if "cross" in params and "xk" in cache:
        hc = napply(params["cross_ln"], x)
        b = x.shape[0]
        q = attn.dense(engine, params["cross"]["q"], hc[:, None, :])
        q = attn._split_heads(q, cfg.n_heads)[:, :, 0, :]       # (B,Hq,hd)
        lengths = jnp.full((b,), enc_len, jnp.int32)
        # this layer's cross K/V, as a stack of one
        o = engine.decode_attention(q, cache["xk"][None], cache["xv"][None],
                                    lengths, layer=0, softcap=cfg.attn_softcap)
        o = attn.dense(engine, params["cross"]["o"],
                       o.reshape(b, cfg.n_heads * cfg.resolved_head_dim))
        x = x + o
    h = napply(params["ln2"], x)
    h, rows = _ffn_apply(engine, params, cfg, spec, h[:, None, :],
                         dropless=True)
    return x + h[:, 0], cache, rows
