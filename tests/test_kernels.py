"""Pallas kernel suite vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (conv_layer, decode_attention, expert_gemm,
                           flash_attention, gemm, kv_write, leakyrelu,
                           maxpool)
from repro.kernels.convlayer.ref import conv_layer_ref
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.expert_gemm.ref import expert_gemm_ref
from repro.kernels.flash_attention.ref import (attention_chunked_ref,
                                               attention_ref)
from repro.kernels.gemm.ref import gemm_ref
from repro.kernels.leakyrelu.ref import leakyrelu_ref
from repro.kernels.maxpool.ref import maxpool_ref


# ------------------------------------------------------------------ gemm
@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (100, 70, 130), (128, 128, 128),
                                   (33, 257, 65), (1, 64, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_gemm_sweep(rng, m, k, n, dtype):
    if dtype == jnp.int8:
        a = jnp.array(rng.integers(-8, 8, (m, k)), dtype)
        b = jnp.array(rng.integers(-8, 8, (k, n)), dtype)
        out = gemm(a, b, block_m=32, block_n=128, block_k=128)
        np.testing.assert_array_equal(out, gemm_ref(a, b))
    else:
        a = jnp.array(rng.standard_normal((m, k)), dtype)
        b = jnp.array(rng.standard_normal((k, n)), dtype)
        out = gemm(a, b, block_m=32, block_n=128, block_k=128)
        ref = gemm_ref(a, b)
        atol = 1e-4 if dtype == jnp.float32 else 0.1
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=atol,
                                   rtol=1e-2)


def test_gemm_alpha_beta(rng):
    a = jnp.array(rng.standard_normal((48, 32)), jnp.float32)
    b = jnp.array(rng.standard_normal((32, 40)), jnp.float32)
    c = jnp.array(rng.standard_normal((48, 40)), jnp.float32)
    out = gemm(a, b, c, alpha=0.5, beta=-1.5, block_m=16, block_n=128,
               block_k=128)
    np.testing.assert_allclose(out, gemm_ref(a, b, c, alpha=0.5, beta=-1.5),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------- conv layer
@pytest.mark.parametrize("h,w,kk,nf,br", [(16, 16, 3, 1, 4), (33, 29, 5, 2, 8),
                                          (64, 64, 7, 4, 16)])
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float32])
def test_conv_layer_sweep(rng, h, w, kk, nf, br, dtype):
    if dtype == jnp.int8:
        x = jnp.array(rng.integers(-5, 5, (3, h, w)), dtype)
        f = jnp.array(rng.integers(-3, 3, (nf, 3, kk, kk)), dtype)
    else:
        x = jnp.array(rng.standard_normal((3, h, w)), dtype)
        f = jnp.array(rng.standard_normal((nf, 3, kk, kk)), dtype)
    out = conv_layer(x, f, negative_slope=0.125, block_rows=br)
    ref = conv_layer_ref(x, f, negative_slope=0.125)
    if dtype == jnp.int8:
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)


# ------------------------------------------------------------ pool / relu
@pytest.mark.parametrize("win,stride", [(2, 2), (3, 2), (3, 3), (4, 1)])
def test_maxpool_sweep(rng, win, stride):
    x = jnp.array(rng.integers(-100, 100, (37, 53)), jnp.int32)
    np.testing.assert_array_equal(
        maxpool(x, win=win, stride=stride, block_rows=8),
        maxpool_ref(x, win=win, stride=stride))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_leakyrelu_sweep(rng, dtype):
    if dtype == jnp.int8:
        x = jnp.array(rng.integers(-100, 100, (17, 300)), dtype)
    else:
        x = jnp.array(rng.standard_normal((17, 300)), dtype)
    np.testing.assert_array_equal(
        leakyrelu(x, negative_slope=0.2),
        leakyrelu_ref(x, negative_slope=0.2))


# -------------------------------------------------------- flash attention
@pytest.mark.parametrize("kwargs", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=37),
    dict(causal=True, softcap=30.0),
    dict(causal=True, window=17, softcap=20.0),
])
def test_flash_attention_variants(rng, kwargs):
    B, Hq, Hkv, S, D = 2, 8, 2, 129, 64
    q = jnp.array(rng.standard_normal((B, Hq, S, D)), jnp.float32)
    k = jnp.array(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.array(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    ref = attention_ref(q, k, v, **kwargs)
    out = flash_attention(q, k, v, block_q=64, block_k=64, **kwargs)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=1e-3)
    chk = attention_chunked_ref(q, k, v, chunk=64, **kwargs)
    np.testing.assert_allclose(chk, ref, atol=2e-3, rtol=1e-3)


@pytest.mark.parametrize("sq,skv", [(64, 64), (128, 256), (8, 8), (100, 52)])
def test_flash_attention_shapes(rng, sq, skv):
    B, Hq, Hkv, D = 1, 4, 4, 32
    q = jnp.array(rng.standard_normal((B, Hq, sq, D)), jnp.float32)
    k = jnp.array(rng.standard_normal((B, Hkv, skv, D)), jnp.float32)
    v = jnp.array(rng.standard_normal((B, Hkv, skv, D)), jnp.float32)
    ref = attention_ref(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=1e-3)


def test_flash_attention_bf16(rng):
    B, H, S, D = 1, 2, 64, 32
    q = jnp.array(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    k = jnp.array(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    v = jnp.array(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    ref = attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2,
                               rtol=3e-2)


# -------------------------------------------------------- decode attention
SWEEP = [pytest.param(w, None, 200, id=str(w)) for w in (None, 50, 16)] + [
    # every layer of a stack (L, B, Hkv, D, S) read in place, against the
    # one-layer call on its slice; at S = 640 the last of three 256-position
    # pages runs past the cache
    pytest.param(w, 3, s, id=f"stacked-{w}-{s}")
    for w in (None, 50, 16) for s in (200, 640)]


@pytest.mark.parametrize("window,layers,S", SWEEP)
def test_decode_attention_sweep(rng, window, layers, S):
    B, Hq, Hkv, D = 2, 8, 2, 64
    lengths = jnp.array([37, S - 10])
    q = jnp.array(rng.standard_normal((B, Hq, D)), jnp.float32)
    if layers is None:
        k = jnp.array(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        v = jnp.array(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        out = decode_attention(q, k, v, lengths, window=window, block_k=64)
        ref = decode_attention_ref(q.reshape(B, Hkv, Hq // Hkv, D), k, v,
                                   lengths, window=window).reshape(B, Hq, D)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=1e-3)
        return
    ks = jnp.array(rng.standard_normal((layers, B, Hkv, D, S)), jnp.float32)
    vs = jnp.array(rng.standard_normal((layers, B, Hkv, D, S)), jnp.float32)
    for layer in range(layers):
        k, v = (jnp.swapaxes(c[layer], 2, 3) for c in (ks, vs))
        want = decode_attention(q, k, v, lengths, window=window, block_k=64)
        for backend in ("pallas", "ref"):
            got = decode_attention(q, ks, vs, lengths, jnp.int32(layer),
                                   window=window, block_k=256,
                                   backend=backend)
            np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


@pytest.mark.parametrize("S", [64, 200, 384])
def test_kv_write_matches_ref(rng, S):
    """Each sequence's column lands at its slot of the named layer, and
    nothing else moves; S = 384 writes in three 128-lane blocks, and at
    S = 200 the second block runs past the cache."""
    L, B, Hkv, D = 3, 4, 2, 16
    ks = jnp.array(rng.standard_normal((L, B, Hkv, D, S)), jnp.float32)
    vs = jnp.array(rng.standard_normal((L, B, Hkv, D, S)), jnp.float32)
    nk = jnp.array(rng.standard_normal((B, Hkv, D, 1)), jnp.float32)
    nv = jnp.array(rng.standard_normal((B, Hkv, D, 1)), jnp.float32)
    slot = jnp.array([0, S - 1, 127 % S, 130 % S], jnp.int32)
    for layer in range(L):
        got = kv_write(ks, vs, nk, nv, slot, jnp.int32(layer))
        want = kv_write(ks, vs, nk, nv, slot, jnp.int32(layer),
                        backend="ref")
        for g, w, old, new in zip(got, want, (ks, vs), (nk, nv)):
            np.testing.assert_array_equal(g, w)
            np.testing.assert_array_equal(
                g[layer, jnp.arange(B), :, :, slot], new[..., 0])
            changed = np.asarray(g != old).any(axis=(2, 3))
            assert changed.sum() <= B and not changed[:layer].any()


def test_decode_attention_mha_and_softcap(rng):
    B, H, S, D = 3, 4, 77, 32
    lengths = jnp.array([1, 40, 77])
    k = jnp.array(rng.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.array(rng.standard_normal((B, H, S, D)), jnp.float32)
    q = jnp.array(rng.standard_normal((B, H, D)), jnp.float32)
    out = decode_attention(q, k, v, lengths, softcap=25.0, block_k=16)
    ref = decode_attention_ref(q.reshape(B, H, 1, D), k, v, lengths,
                               softcap=25.0).reshape(B, H, D)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=1e-3)


# ------------------------------------------------------------ expert gemm
@pytest.mark.parametrize("sizes,bm", [
    ([5, 0, 17, 0, 3, 32, 0, 1], 16),     # ragged, empty groups between
    ([0, 0, 0, 9], 8),                    # only the last group has rows
    ([0, 0, 0], 16),                      # no rows at all
    ([16, 16], 16),                       # whole tiles
], ids=["ragged", "last-only", "empty", "whole-tiles"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_gemm_ragged_groups(rng, sizes, bm, dtype):
    """The grouped kernel (interpret mode) and its oracle give each group's
    rows times that group's weights, over tiles padded per group and a
    spare tile past the last."""
    sizes = np.asarray(sizes, np.int32)
    g, k, n = len(sizes), 48, 256
    tiles = -(-sizes // bm)
    x = np.zeros((int(tiles.sum() + 2) * bm, k), np.float32)
    starts = np.concatenate([[0], np.cumsum(tiles)[:-1]]) * bm
    for e, (s0, sz) in enumerate(zip(starts, sizes)):
        x[s0:s0 + sz] = rng.standard_normal((sz, k))
    w = rng.standard_normal((g, k, n)).astype(np.float32)
    xj, wj = jnp.asarray(x, dtype), jnp.asarray(w, dtype)
    got = expert_gemm(xj, wj, jnp.asarray(sizes), block_m=bm)
    ref = expert_gemm_ref(xj, wj, jnp.asarray(sizes), block_m=bm)
    assert got.shape == ref.shape == (x.shape[0], n) and got.dtype == dtype
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    xr, wr = np.asarray(xj, np.float32), np.asarray(wj, np.float32)
    for e, (s0, sz) in enumerate(zip(starts, sizes)):
        want = xr[s0:s0 + sz] @ wr[e]
        for out in (got, ref):
            np.testing.assert_allclose(np.asarray(out, np.float32)[s0:s0 + sz],
                                       want, atol=tol, rtol=1e-2)
