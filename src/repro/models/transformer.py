"""Model assembly: decoder-only LM (all families) and encoder-decoder (whisper).

Depth is executed as ``lax.scan`` over *periods* of the repeating layer
pattern (per-position parameter stacks with a leading ``n_periods`` axis), so
HLO size is independent of layer count — essential for the 62/64/72-layer
assigned configs — and the activation-checkpoint policy applies per period.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.engine import ArcaneEngine
from repro.models import blocks as blk
from repro.models.layers import (embed, embedding_init, make_norm,
                                 sinusoidal_positions, unembed)

PyTree = Any


def _stack_init(key, n: int, init_fn):
    """Initialise ``n`` copies of a block, stacked on a leading axis.

    One vmapped init writes the stack directly: no per-layer copies to
    concatenate, and one traced block instead of ``n``."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _index_tree(tree: PyTree, i):
    return jax.tree.map(lambda x: x[i], tree)


class LM:
    """Decoder-only (optionally enc-dec / vision-prefixed) language model."""

    def __init__(self, cfg: ModelConfig, engine: ArcaneEngine,
                 *, remat: bool = True, unroll: bool = False):
        self.cfg = cfg
        self.engine = engine
        self.remat = remat
        # unroll=True replaces the period scan with a Python loop — used by
        # the dry-run's depth-extrapolation compiles (cost_analysis counts a
        # while-loop body once regardless of trip count).
        self.unroll = unroll

    def _scan(self, fn, carry, xs):
        if not self.unroll:
            return jax.lax.scan(fn, carry, xs)
        n = jax.tree.leaves(xs)[0].shape[0]
        ys = []
        for i in range(n):
            carry, y = fn(carry, jax.tree.map(lambda x, i=i: x[i], xs))
            ys.append(y)
        if all(y is None for y in ys):
            return carry, None
        return carry, jax.tree.map(lambda *zs: jnp.stack(zs), *ys)

    # ------------------------------------------------------------- params
    def init_params(self, key) -> PyTree:
        cfg = self.cfg
        ninit, _ = make_norm(cfg.norm)
        keys = jax.random.split(key, 8)
        params: dict[str, Any] = {
            "embed": embedding_init(keys[0], cfg.vocab, cfg.d_model,
                                    cfg.pdtype),
            "final_norm": ninit(cfg.d_model, cfg.pdtype),
        }
        cross = cfg.enc_dec
        params["blocks"] = tuple(
            _stack_init(
                jax.random.fold_in(keys[1], i), cfg.n_periods,
                functools.partial(blk.block_init, cfg=cfg, spec=spec,
                                  cross=cross))
            for i, spec in enumerate(cfg.pattern)
        )
        if cfg.enc_dec:
            from repro.configs.base import LayerSpec
            enc_spec = LayerSpec(kind="attn")
            params["enc_blocks"] = (
                _stack_init(keys[2], cfg.n_enc_layers,
                            functools.partial(blk.block_init, cfg=cfg,
                                              spec=enc_spec)),
            )
            params["enc_final_norm"] = ninit(cfg.d_model, cfg.pdtype)
        if not cfg.tie_embeddings:
            params["unembed"] = embedding_init(keys[3], cfg.vocab,
                                               cfg.d_model, cfg.pdtype)
        return params

    def param_shapes(self) -> PyTree:
        return jax.eval_shape(
            lambda k: self.init_params(k), jax.random.key(0))

    # ------------------------------------------------------------ forward
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        x = embed(params["embed"], batch["tokens"], scale=cfg.embed_scale)
        if cfg.vision_prefix:
            x = jnp.concatenate(
                [batch["vision_embeds"].astype(x.dtype), x], axis=1)
        if cfg.enc_dec:
            # whisper decoder uses absolute positions (rope_fraction = 0)
            pos = sinusoidal_positions(x.shape[1], cfg.d_model)
            x = x + pos[None].astype(x.dtype)
        return x.astype(cfg.cdtype)

    def _encoder(self, params, batch):
        cfg = self.cfg
        from repro.configs.base import LayerSpec
        x = batch["audio_embeds"].astype(cfg.cdtype)
        s = x.shape[1]
        pos_tab = sinusoidal_positions(s, cfg.d_model).astype(x.dtype)
        x = x + pos_tab[None]
        positions = jnp.arange(s)
        enc_spec = LayerSpec(kind="attn")

        def period_fn(carry, bp):
            h = carry
            h, _ = blk.block_forward(self.engine, bp, cfg, enc_spec,
                                     h, positions, causal=False)
            return h, None

        fn = jax.checkpoint(period_fn) if self.remat else period_fn
        x, _ = self._scan(fn, x, params["enc_blocks"][0])
        _, napply = make_norm(cfg.norm)
        return napply(params["enc_final_norm"], x)

    def forward(self, params, batch) -> tuple[jax.Array, jax.Array]:
        """→ (logits (B, S, V) f32, moe_aux)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        s = x.shape[1]
        positions = jnp.arange(s)
        enc_out = self._encoder(params, batch) if cfg.enc_dec else None

        def period_fn(carry, bps):
            h, aux = carry
            for i, spec in enumerate(cfg.pattern):
                h, a = blk.block_forward(self.engine, bps[i], cfg, spec, h,
                                         positions, enc_out=enc_out)
                aux = aux + a
            return (h, aux), None

        fn = jax.checkpoint(period_fn) if self.remat else period_fn
        (x, aux), _ = self._scan(fn, (x, jnp.float32(0.0)), params["blocks"])
        _, napply = make_norm(cfg.norm)
        x = napply(params["final_norm"], x)
        table = params["unembed" if "unembed" in params else "embed"]
        logits = unembed(self.engine, table, x, softcap=cfg.final_softcap)
        if cfg.vision_prefix:
            logits = logits[:, cfg.vision_prefix:]
        return logits, aux

    def loss(self, params, batch) -> tuple[jax.Array, dict]:
        logits, aux = self.forward(params, batch)
        tokens = batch["tokens"]
        targets = tokens[:, 1:]
        lg = logits[:, :-1]
        mask = batch.get("loss_mask")
        mask = mask[:, 1:] if mask is not None else jnp.ones_like(
            targets, jnp.float32)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mask
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = nll.sum() / denom
        total = ce + aux
        return total, {"ce": ce, "aux": aux,
                       "tokens": denom}

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, max_len: int, *, dtype=None,
                   enc_len: int = 0) -> tuple:
        cfg = self.cfg
        dtype = dtype or cfg.cdtype

        def one(spec):
            # caches start at zero: allocate the (n_periods, ...) stack once
            shapes = jax.eval_shape(lambda: blk.init_block_cache(
                cfg, spec, batch, max_len, dtype, cross_len=enc_len))
            return jax.tree.map(
                lambda x: jnp.zeros((cfg.n_periods, *x.shape), x.dtype),
                shapes)

        return tuple(one(spec) for spec in cfg.pattern)

    def cache_shapes(self, batch: int, max_len: int, *, dtype=None,
                     enc_len: int = 0):
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_len, dtype=dtype,
                                    enc_len=enc_len))

    def prefill(self, params, batch, cache) -> tuple[jax.Array, tuple]:
        """Process the full prompt; returns (last-position logits, cache)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        s = x.shape[1]
        positions = jnp.arange(s)
        enc_out = self._encoder(params, batch) if cfg.enc_dec else None

        def period_fn(h, xs):
            bps, caches = xs
            new_caches = []
            for i, spec in enumerate(cfg.pattern):
                h, c = blk.block_prefill(self.engine, bps[i], cfg, spec, h,
                                         positions, caches[i],
                                         enc_out=enc_out)
                new_caches.append(c)
            return h, tuple(new_caches)

        x, cache = self._scan(period_fn, x, (params["blocks"], cache))
        _, napply = make_norm(cfg.norm)
        x = napply(params["final_norm"], x[:, -1:])
        table = params["unembed" if "unembed" in params else "embed"]
        logits = unembed(self.engine, table, x, softcap=cfg.final_softcap)
        return logits[:, 0], cache

    def decode_step(self, params, tokens: jax.Array, position: jax.Array,
                    cache: tuple, *, enc_len: int = 0):
        """tokens: (B,) int32; position: (B,) → (logits (B, V), cache)."""
        cfg = self.cfg
        x = embed(params["embed"], tokens, scale=cfg.embed_scale)
        if cfg.enc_dec:
            from repro.models.layers import sinusoidal_at
            x = x + sinusoidal_at(position, cfg.d_model).astype(x.dtype)
        x = x.astype(cfg.cdtype)

        def period_fn(h, xs):
            bps, caches = xs
            new_caches = []
            for i, spec in enumerate(cfg.pattern):
                h, c = blk.block_decode(self.engine, bps[i], cfg, spec, h,
                                        position, caches[i],
                                        enc_len=enc_len or None)
                new_caches.append(c)
            return h, tuple(new_caches)

        x, cache = self._scan(period_fn, x, (params["blocks"], cache))
        _, napply = make_norm(cfg.norm)
        x = napply(params["final_norm"], x)
        table = params["unembed" if "unembed" in params else "embed"]
        logits = unembed(self.engine, table, x, softcap=cfg.final_softcap)
        return logits, cache
