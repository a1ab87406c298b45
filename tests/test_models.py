"""Per-arch smoke tests (deliverable f) + the golden incremental-decode test."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core.engine import ArcaneEngine
from repro.models import blocks as blk
from repro.models.layers import embed, make_norm, sinusoidal_at, unembed
from repro.models.transformer import LM
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.train.step import make_train_step

ENGINE = ArcaneEngine(backend="ref")


def make_batch(cfg, rng, b=2, s=32, dtype=None):
    batch = {"tokens": jnp.array(rng.integers(0, cfg.vocab, (b, s)))}
    dt = dtype or cfg.cdtype
    if cfg.vision_prefix:
        batch["vision_embeds"] = jnp.array(
            rng.standard_normal((b, cfg.vision_prefix, cfg.d_model)), dt)
    if cfg.enc_dec:
        batch["audio_embeds"] = jnp.array(
            rng.standard_normal((b, s, cfg.d_model)), dt)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_and_train_step(arch, rng):
    """Reduced config: one forward + one train step, shapes + no NaNs."""
    cfg = get_smoke_config(arch)
    model = LM(cfg, ENGINE)
    params = model.init_params(jax.random.key(0))
    batch = make_batch(cfg, rng)
    logits, aux = jax.jit(model.forward)(params, batch)
    assert logits.shape == (2, 32, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    opt_cfg = AdamWConfig(total_steps=10, warmup_steps=2)
    opt = adamw_init(opt_cfg, params)
    step = jax.jit(make_train_step(model, opt_cfg))
    params2, opt2, metrics = step(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    # parameters actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_golden_incremental_decode(arch, rng):
    """Prefill + token-by-token decode must match the parallel forward."""
    cfg = get_smoke_config(arch)
    repl = dict(param_dtype="float32", compute_dtype="float32")
    if cfg.moe is not None:   # avoid capacity-drop divergence between paths
        repl["moe"] = dataclasses.replace(cfg.moe, capacity_factor=8.0)
    cfg = dataclasses.replace(cfg, **repl)
    model = LM(cfg, ENGINE)
    params = model.init_params(jax.random.key(1))
    B, S = 2, 16
    toks = jnp.array(rng.integers(0, cfg.vocab, (B, S)))
    batch = make_batch(cfg, rng, B, S, dtype=jnp.float32)
    batch["tokens"] = toks
    logits_full, _ = jax.jit(model.forward)(params, batch)
    P = S - 4
    off = cfg.vision_prefix
    pb = dict(batch)
    pb["tokens"] = toks[:, :P]
    enc = S if cfg.enc_dec else 0
    cache = model.init_cache(B, 64, dtype=jnp.float32, enc_len=enc)
    lg, cache = jax.jit(model.prefill)(params, pb, cache)
    errs = [float(jnp.max(jnp.abs(lg - logits_full[:, P - 1])))]
    step = jax.jit(lambda p, t, po, c: model.decode_step(p, t, po, c,
                                                         enc_len=enc))
    for i in range(P, S):
        pos = jnp.full((B,), off + i, jnp.int32)
        lg, cache = step(params, toks[:, i], pos, cache)
        if i < S - 1:
            errs.append(float(jnp.max(jnp.abs(lg - logits_full[:, i]))))
    assert max(errs) < 2e-3, f"{arch}: {errs}"


def test_full_configs_param_counts():
    """Full (non-smoke) configs expose sane analytic parameter counts."""
    expect = {
        "granite-moe-1b-a400m": (1.0e9, 1.7e9),
        "llama4-scout-17b-a16e": (90e9, 120e9),
        "whisper-large-v3": (1.2e9, 2.2e9),
        "stablelm-3b": (2.5e9, 3.8e9),
        "gemma2-9b": (8.0e9, 11e9),
        "minicpm3-4b": (3.4e9, 5.0e9),
        "qwen2.5-32b": (30e9, 36e9),
        "internvl2-1b": (0.4e9, 1.2e9),
        "jamba-1.5-large-398b": (330e9, 440e9),
        "rwkv6-1.6b": (1.3e9, 2.2e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:.3e} outside [{lo:.1e},{hi:.1e}]"


def test_moe_active_lt_total():
    for arch in ("granite-moe-1b-a400m", "llama4-scout-17b-a16e",
                 "jamba-1.5-large-398b"):
        cfg = get_config(arch)
        assert cfg.active_param_count() < cfg.param_count()


def test_engine_trace_records_xmnmc_words(rng):
    eng = ArcaneEngine(backend="ref", record=True)
    cfg = get_smoke_config("qwen2.5-32b")
    model = LM(cfg, eng)
    params = model.init_params(jax.random.key(0))
    batch = {"tokens": jnp.array(rng.integers(0, cfg.vocab, (1, 8)))}
    model.forward(params, batch)   # trace eagerly
    assert len(eng.trace) > 0
    mnems = {t.mnemonic for t in eng.trace}
    assert any(m.startswith("xmk0") for m in mnems)   # GeMM dispatches
    for t in eng.trace:
        assert t.word & 0x7F == 0x5B                  # all Custom-2


def test_ring_decode_matches_forward(rng):
    """Ring-buffer local KV cache (§Perf iteration 5) must be decode-exact."""
    cfg = get_smoke_config("gemma2-9b")
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32",
                              ring_local_cache=True, local_window=8)
    model = LM(cfg, ENGINE)
    params = model.init_params(jax.random.key(1))
    B, S = 2, 24
    toks = jnp.array(rng.integers(0, cfg.vocab, (B, S)))
    logits_full, _ = jax.jit(model.forward)(params, {"tokens": toks})
    P = S - 8
    cache = model.init_cache(B, 64, dtype=jnp.float32)
    assert cache[0]["k"].shape[4] == 8      # local layer ring is window-sized
    lg, cache = jax.jit(model.prefill)(params, {"tokens": toks[:, :P]}, cache)
    errs = [float(jnp.max(jnp.abs(lg - logits_full[:, P - 1])))]
    step = jax.jit(model.decode_step)
    for i in range(P, S):
        pos = jnp.full((B,), i, jnp.int32)
        lg, cache = step(params, toks[:, i], pos, cache)
        if i < S - 1:
            errs.append(float(jnp.max(jnp.abs(lg - logits_full[:, i]))))
    assert max(errs) < 2e-3, errs


class SlicedCacheEngine(ArcaneEngine):
    """The K/V write and read as they were before the cache was carried:
    each new column goes into the layer's own sliced cache with a plain
    ``.at[].set`` per sequence, and attention reads a row-major copy of the
    layer (``decode_attention_ref``, or the one-layer kernel call on the
    pallas path). Shares no write or read path with the in-place step."""

    def kv_write(self, cache_k, cache_v, new_k, new_v, slot, layer):
        def put(cache, new):
            for i in range(cache.shape[1]):
                cache = cache.at[layer, i, :, :, slot[i]].set(new[i, ..., 0])
            return cache
        return put(cache_k, new_k), put(cache_v, new_v)

    def decode_attention(self, q, k, v, lengths, *, layer=None, **kw):
        if layer is not None:
            k, v = (jnp.swapaxes(c[layer], -1, -2) for c in (k, v))
        return super().decode_attention(q, k, v, lengths, **kw)


def decode_step_xs_ys(model, params, tokens, position, cache, *, enc_len=0):
    """The decode step as it was before the cache rode in the scan's carry:
    each layer's cache is sliced out of the scan's ``xs`` and its new cache
    stacked into ``ys``. Each block sees a one-layer stack at layer 0, and
    the K/V go through ``SlicedCacheEngine``."""
    cfg = model.cfg
    engine = SlicedCacheEngine(model.engine.backend)
    x = embed(params["embed"], tokens, scale=cfg.embed_scale)
    if cfg.enc_dec:
        x = x + sinusoidal_at(position, cfg.d_model).astype(x.dtype)
    x = x.astype(cfg.cdtype)

    def period_fn(h, xs):
        bps, caches = xs
        new = []
        for i, spec in enumerate(cfg.pattern):
            one = jax.tree.map(lambda c: c[None], caches[i])
            h, c = blk.block_decode(engine, bps[i], cfg, spec, h,
                                    position, one, 0,
                                    enc_len=enc_len or None)
            new.append(jax.tree.map(lambda c: c[0], c))
        return h, tuple(new)

    x, cache = jax.lax.scan(period_fn, x, (params["blocks"], cache))
    _, napply = make_norm(cfg.norm)
    x = napply(params["final_norm"], x)
    table = params["unembed" if "unembed" in params else "embed"]
    return unembed(engine, table, x, softcap=cfg.final_softcap), cache


INPLACE_CASES = [
    ("stablelm-3b", {}, "ref"),
    ("stablelm-3b", {}, "pallas"),
    ("gemma2-9b", {"ring_local_cache": True, "local_window": 8}, "ref"),
    ("gemma2-9b", {"ring_local_cache": True, "local_window": 8}, "pallas"),
    ("minicpm3-4b", {}, "ref"),
    ("jamba-1.5-large-398b", {}, "ref"),
    ("whisper-large-v3", {}, "ref"),
]


@pytest.mark.parametrize(
    "arch,over,backend", INPLACE_CASES,
    ids=[f"{a}-{b}{'-ring' if o else ''}" for a, o, b in INPLACE_CASES])
def test_inplace_decode_matches_xs_ys(arch, over, backend):
    """The carried, in-place decode step computes what the xs/ys scan did:
    logits within 1e-5 and bit-identical caches, over several steps at
    ragged positions (past the ring's window where there is one)."""
    cfg = dataclasses.replace(get_smoke_config(arch), param_dtype="float32",
                              compute_dtype="float32", **over)
    model = LM(cfg, ArcaneEngine(backend=backend))
    params = model.init_params(jax.random.key(3))
    B, max_len = 3, 24
    enc = 8 if cfg.enc_dec else 0
    shapes = model.cache_shapes(B, max_len, dtype=jnp.float32, enc_len=enc)
    keys = iter(jax.random.split(jax.random.key(4), 64))
    cache = jax.tree.map(
        lambda s: 0.5 * jax.random.normal(next(keys), s.shape, s.dtype),
        shapes)
    carried = jax.jit(functools.partial(model.decode_step, enc_len=enc))
    xs_ys = jax.jit(functools.partial(decode_step_xs_ys, model, enc_len=enc))
    position = jnp.array([5, 17, 11], jnp.int32)
    rng = np.random.default_rng(5)
    want_cache = cache
    for _ in range(3):
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, B), jnp.int32)
        got, cache = carried(params, tokens, position, cache)
        want, want_cache = xs_ys(params, tokens, position, want_cache)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
        for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
            np.testing.assert_array_equal(a, b)
        position = position + 1


def _decode_matches_forward(cfg, rng, *, B=2, S=16, P=10):
    """Prefill of P tokens, then decode through the cache to S: every
    step's logits are the parallel forward's."""
    model = LM(cfg, ENGINE)
    params = model.init_params(jax.random.key(1))
    toks = jnp.array(rng.integers(0, cfg.vocab, (B, S)))
    full, _ = jax.jit(model.forward)(params, {"tokens": toks})
    cache = model.init_cache(B, 32, dtype=jnp.float32)
    lg, cache = jax.jit(model.prefill)(params, {"tokens": toks[:, :P]}, cache)
    errs = [float(jnp.max(jnp.abs(lg - full[:, P - 1])))]
    step = jax.jit(model.decode_step)
    for i in range(P, S - 1):
        lg, cache = step(params, toks[:, i], jnp.full((B,), i, jnp.int32), cache)
        errs.append(float(jnp.max(jnp.abs(lg - full[:, i]))))
    assert max(errs) < 2e-3, errs
    return model, params, cache


def test_direct_q_mla_with_leading_dense_layer(rng):
    """MLA with a direct q projection (q_lora_rank None) and one leading
    dense layer ahead of the periods: its own parameter stack and the last
    cache entry, and prefill + decode agree with forward."""
    cfg = dataclasses.replace(
        get_smoke_config("minicpm3-4b"), n_layers=3, n_dense_layers=1,
        mla=dataclasses.replace(get_smoke_config("minicpm3-4b").mla,
                                q_lora_rank=None),
        param_dtype="float32", compute_dtype="float32")
    model, params, cache = _decode_matches_forward(cfg, rng)
    assert "q" in params["blocks"][0]["attn"] and "q_down" not in params["blocks"][0]["attn"]
    assert params["dense_blocks"]["attn"]["q"]["w"].shape[0] == 1
    assert len(cache) == 2 and cache[-1]["c"].shape[0] == 1
    assert cache[0]["c"].shape[0] == cfg.n_periods == 2


@pytest.mark.parametrize("arch", ["stablelm-3b", "minicpm3-4b"])
def test_no_leading_dense_layers_no_extra_program(arch):
    """With no leading dense layers and a q latent, the model keeps one
    scan per program, one cache entry per pattern position, and no
    direct q projection."""
    cfg = get_smoke_config(arch)
    model = LM(cfg, ENGINE)
    shapes = model.param_shapes()
    assert "dense_blocks" not in shapes
    cache = model.cache_shapes(2, 32)
    assert len(cache) == len(cfg.pattern) == len(cfg.cache_specs)
    if cfg.mla is not None:
        assert "q_down" in shapes["blocks"][0]["attn"]
    jaxpr = jax.make_jaxpr(model.decode_step)(
        shapes, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), cache)
    assert [e.primitive.name for e in jaxpr.eqns].count("scan") == 1


def test_moonlight_param_counts():
    """The published 16B-A3B: every routed expert, the shared ones, the
    dense first layer, direct q and untied embeddings counted."""
    cfg = get_config("moonlight-16b-a3b")
    assert cfg.param_count() == pytest.approx(15.96e9, rel=0.01)
    assert cfg.active_param_count() == pytest.approx(2.91e9, rel=0.02)
    held = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=(0, 8)))
    assert held.param_count() == cfg.param_count()   # the model, not the chip's share
