"""SSM scan correctness (chunk invariance, naive-ref parity) + MoE invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import LayerSpec, MambaConfig, ModelConfig, MoEConfig
from repro.core.engine import ArcaneEngine
from repro.models.mamba import mamba_forward, mamba_init
from repro.models.mlp import mlp
from repro.models.moe import moe, moe_init, route
from repro.models.rwkv6 import rwkv_init, rwkv_time_mix

ENGINE = ArcaneEngine(backend="ref")


def _mamba_cfg(chunk):
    return ModelConfig(
        name="m", family="ssm", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=4, d_ff=64, vocab=64,
        pattern=(LayerSpec(kind="mamba"),),
        mamba=MambaConfig(d_state=4, d_conv=4, expand=2, chunk=chunk),
        param_dtype="float32", compute_dtype="float32")


def test_mamba_chunk_invariance(rng):
    """The chunked scan must be invariant to chunk size (math identity)."""
    x = jnp.asarray(rng.standard_normal((2, 32, 32)), jnp.float32)
    p = mamba_init(jax.random.key(0), _mamba_cfg(32))
    outs = []
    for chunk in (4, 8, 16, 32):
        cfg = _mamba_cfg(chunk)
        y, h = mamba_forward(ENGINE, p, cfg, x)
        outs.append((np.asarray(y), np.asarray(h)))
    for y, h in outs[1:]:
        np.testing.assert_allclose(y, outs[0][0], atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(h, outs[0][1], atol=1e-4, rtol=1e-4)


def test_mamba_matches_naive_recurrence(rng):
    """Associative-scan implementation vs a step-by-step reference."""
    cfg = _mamba_cfg(8)
    p = mamba_init(jax.random.key(1), cfg)
    x = jnp.asarray(rng.standard_normal((1, 16, 32)), jnp.float32)
    y, h_last = mamba_forward(ENGINE, p, cfg, x)

    # naive: replicate the terms then a python recurrence
    from repro.models.mamba import _causal_conv, _selective_terms
    from repro.models.layers import dense
    xz = dense(ENGINE, p["in_proj"], x)
    xi, z = jnp.split(xz, 2, axis=-1)
    xc, _ = _causal_conv(p, xi)
    xc = jax.nn.silu(xc).astype(x.dtype)
    decay, contrib, cmat = _selective_terms(ENGINE, p, cfg, xc)
    h = np.zeros(decay.shape[2:], np.float32)          # (di, ds)
    ys = []
    for t in range(16):
        h = np.asarray(decay[0, t]) * h + np.asarray(contrib[0, t])
        ys.append(h @ np.asarray(cmat[0, t]))
    ys = np.stack(ys)                                   # (S, di)
    ys = ys + np.asarray(p["D"]) * np.asarray(xc[0])
    ref = ys * np.asarray(jax.nn.silu(z[0]))
    got_pre = dense(ENGINE, p["out_proj"],
                    jnp.asarray(ref[None], jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(got_pre),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h_last[0]), h, atol=1e-4)


def test_rwkv_chunk_invariance(rng):
    cfg = get_smoke_config("rwkv6-1.6b")
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    p = rwkv_init(jax.random.key(0), cfg)
    x = jnp.asarray(rng.standard_normal((2, 32, cfg.d_model)), jnp.float32)
    outs = []
    for chunk in (4, 16, 32):
        cfg2 = dataclasses.replace(
            cfg, rwkv=dataclasses.replace(cfg.rwkv, chunk=chunk))
        y, S, _ = rwkv_time_mix(ENGINE, p, cfg2, x)
        outs.append((np.asarray(y), np.asarray(S)))
    for y, S in outs[1:]:
        np.testing.assert_allclose(y, outs[0][0], atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(S, outs[0][1], atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------------- MoE
def _moe_cfg(cap=8.0, e=4, k=2, **moe_kw):
    return ModelConfig(
        name="moe", family="moe", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=4, d_ff=48, vocab=64,
        pattern=(LayerSpec(kind="attn", moe=True),),
        moe=MoEConfig(n_experts=e, top_k=k, capacity_factor=cap, **moe_kw),
        param_dtype="float32", compute_dtype="float32")


def _dense_loop(p, cfg, t):
    """Every held expert over every token, weighted by its gate where the
    token chose it; the router written out again; the shared experts once."""
    m = cfg.moe
    logits = np.asarray(t, np.float64) @ np.asarray(p["router"]["w"], np.float64)
    if m.router == "sigmoid":
        s = 1.0 / (1.0 + np.exp(-logits))
        ids = np.argsort(-(s + np.asarray(p["router"]["bias"])), -1)[:, :m.top_k]
    else:
        s = np.exp(logits - logits.max(-1, keepdims=True))
        s /= s.sum(-1, keepdims=True)
        ids = np.argsort(-s, -1)[:, :m.top_k]
    w = np.take_along_axis(s, ids, -1)
    w = w / w.sum(-1, keepdims=True) * m.routed_scale
    lo, hi = m.held_range
    ref = np.zeros(t.shape, np.float64)
    for e in range(lo, hi):
        ye = np.asarray(jax.nn.silu(t @ p["gate"][e - lo]) * (t @ p["up"][e - lo])
                        @ p["down"][e - lo], np.float64)
        ref += (w * (ids == e)).sum(-1)[:, None] * ye
    if "shared" in p:
        f = p["shared"]
        ref += np.asarray(jax.nn.silu(t @ f["gate"]["w"]) * (t @ f["up"]["w"])
                          @ f["down"]["w"], np.float64)
    return ref


def _with_bias(p, cfg):
    """Router bias of the scores' own size, so that it steers the choice."""
    if cfg.moe.router == "sigmoid":
        p["router"]["bias"] = 0.1 * jax.random.normal(
            jax.random.key(9), p["router"]["bias"].shape)
    return p


MOE_CASES = {
    "softmax": dict(),
    "sigmoid-bias": dict(router="sigmoid", routed_scale=2.5),
    "shared": dict(router="sigmoid", routed_scale=2.5, n_shared=2,
                   d_expert=24),
    "held": dict(router="sigmoid", routed_scale=2.5, n_shared=1,
                 d_expert=24, held=(3, 7)),
    "softmax-held": dict(n_shared=1, held=(0, 5)),
}


@pytest.mark.parametrize("engine", ["ref", "pallas"])
@pytest.mark.parametrize("case", sorted(MOE_CASES))
def test_moe_layer_matches_dense_loop(rng, case, engine):
    """Dropless dispatch (expert_gemm on either backend, interpret mode
    for pallas) and capacity dispatch with room for every token both give
    the dense loop over the held experts, for each router, with shared
    experts and a held subset."""
    cfg = _moe_cfg(cap=16.0, e=8, k=3, **MOE_CASES[case])
    p = _with_bias(moe_init(jax.random.key(0), cfg), cfg)
    x = jnp.asarray(rng.standard_normal((2, 12, 32)), jnp.float32)
    ref = _dense_loop(p, cfg, np.asarray(x).reshape(-1, 32))
    outs = [moe(ArcaneEngine(backend=engine), p, cfg, x, dropless=True)[0]]
    if engine == "ref":
        outs.append(moe(ENGINE, p, cfg, x)[0])
    for out in outs:
        np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), ref,
                                   atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("case", ["softmax", "held", "softmax-held"])
def test_moe_dropless_counts_rows_per_held_expert(rng, case):
    """Serving's aux output: the rows each held expert received, the
    (token, expert) pairs the router sent it; the capacity dispatch's aux
    stays the balance loss, a float32 scalar."""
    cfg = _moe_cfg(cap=16.0, e=8, k=3, **MOE_CASES[case])
    p = _with_bias(moe_init(jax.random.key(0), cfg), cfg)
    x = jnp.asarray(rng.standard_normal((2, 12, 32)), jnp.float32)
    _, rows = moe(ENGINE, p, cfg, x, dropless=True)
    _, ids, _ = route(p, cfg, x.reshape(-1, 32))
    lo, hi = cfg.moe.held_range
    want = np.bincount(np.asarray(ids).ravel(), minlength=8)[lo:hi]
    assert rows.dtype == jnp.int32 and rows.shape == (hi - lo,)
    np.testing.assert_array_equal(np.asarray(rows), want)
    _, aux = moe(ENGINE, p, cfg, x)
    assert aux.dtype == jnp.float32 and aux.shape == ()


def test_moe_sigmoid_bias_steers_choice_not_gates(rng):
    """noaux_tc: the bias moves which experts are chosen; the gates are
    the unbiased scores of the chosen, renormalised and scaled."""
    cfg = _moe_cfg(e=8, k=2, router="sigmoid", routed_scale=2.0)
    p = moe_init(jax.random.key(0), cfg)
    t = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    _, ids0, g0 = route(p, cfg, t)
    p["router"]["bias"] = p["router"]["bias"].at[5].set(10.0)
    _, ids1, g1 = route(p, cfg, t)
    assert (np.asarray(ids1) == 5).any(-1).all()
    assert not (np.asarray(ids0) == 5).any(-1).all()
    s = jax.nn.sigmoid(t @ p["router"]["w"])
    want = np.take_along_axis(np.asarray(s), np.asarray(ids1), -1)
    np.testing.assert_allclose(np.asarray(g1), 2.0 * want / want.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g0).sum(-1), 2.0, rtol=1e-5)


@pytest.mark.parametrize("dropless", [True, False], ids=["dropless", "capacity"])
def test_moe_held_shares_add_up_to_the_uncut_layer(rng, dropless):
    """Expert parallelism on one chip's share: the outputs of 8 disjoint
    held shares of 16 experts, the shared experts counted once, add up to
    the layer that holds every expert."""
    over = dict(router="sigmoid", routed_scale=2.446, n_shared=2, d_expert=24)
    full = _moe_cfg(cap=32.0, e=16, k=4, **over)
    p = _with_bias(moe_init(jax.random.key(1), full), full)
    x = jnp.asarray(rng.standard_normal((2, 10, 32)), jnp.float32)
    whole, _ = moe(ENGINE, p, full, x, dropless=dropless)
    shared = np.asarray(mlp(ENGINE, p["shared"], full, x))
    total = -7 * shared
    for i in range(8):
        cut = _moe_cfg(cap=32.0, e=16, k=4, held=(2 * i, 2 * i + 2), **over)
        part = {**p, **{w: p[w][2 * i: 2 * i + 2] for w in ("gate", "up", "down")}}
        total = total + np.asarray(moe(ENGINE, part, cut, x, dropless=dropless)[0])
    np.testing.assert_allclose(total, np.asarray(whole), atol=2e-5, rtol=1e-4)


def test_moe_matches_dense_reference_at_high_capacity(rng):
    """With no drops, capacity dispatch must equal the dense top-k formula."""
    cfg = _moe_cfg(cap=16.0)
    p = moe_init(jax.random.key(0), cfg)
    x = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
    out, aux = moe(ENGINE, p, cfg, x)
    # dense reference: every expert computes everything, weighted combine
    t = x.reshape(-1, 32)
    logits = t @ np.asarray(p["router"]["w"])
    probs = jax.nn.softmax(jnp.asarray(logits), -1)
    w, ids = jax.lax.top_k(probs, 2)
    w = w / w.sum(-1, keepdims=True)
    ref = np.zeros_like(t)
    for e in range(4):
        g = np.tanh(0)  # placeholder
        ge = jax.nn.silu(t @ p["gate"][e]) * (t @ p["up"][e])
        ye = np.asarray(ge @ p["down"][e])
        for slot in range(2):
            mask = (np.asarray(ids[:, slot]) == e)
            ref[mask] += np.asarray(w[:, slot])[mask, None] * ye[mask]
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), ref,
                               atol=2e-4, rtol=2e-3)
    assert float(aux) >= 0.0


def test_moe_capacity_drops_tokens(rng):
    """Tiny capacity must drop contributions (outputs differ from cap=16)."""
    p = moe_init(jax.random.key(0), _moe_cfg())
    x = jnp.asarray(rng.standard_normal((4, 16, 32)), jnp.float32)
    hi, _ = moe(ENGINE, p, _moe_cfg(cap=16.0), x)
    lo, _ = moe(ENGINE, p, _moe_cfg(cap=0.25), x)
    assert not np.allclose(np.asarray(hi), np.asarray(lo))


def test_moe_aux_loss_uniform_router_near_one(rng):
    """Balanced routing → aux ≈ coef (E · Σ 1/E · k/E · ... normalised)."""
    cfg = _moe_cfg()
    p = moe_init(jax.random.key(2), cfg)
    x = jnp.asarray(rng.standard_normal((8, 32, 32)), jnp.float32)
    _, aux = moe(ENGINE, p, cfg, x)
    # with near-uniform routing aux ≈ coef * E * (1/E) * k = coef * k
    assert 0.0 < float(aux) < 4 * cfg.moe.router_aux_coef * cfg.moe.top_k
