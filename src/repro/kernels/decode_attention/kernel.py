"""Cache-resident decode attention Pallas kernel (single-token GQA decode).

The serving-side embodiment of ARCANE's near-memory idea: the KV cache is this
framework's "last-level cache", and decode attention is a complex instruction
executed *where the cache lives* — one fused sweep over cache pages with the
online-softmax state in VMEM. No gather, no concat, no head-broadcast
materialisation: the q-head group belonging to one KV head attends inside a
single program.

q: (B, Hkv, G, D)  — G = Hq / Hkv query heads per KV head,
k, v: (B, Hkv, S, D) — one layer's cache, or
      (L, B, Hkv, D, S) — every layer's cache, stacked and stored with the
      sequence minor, as the model keeps its K/V cache; the kernel reads
      layer ``layer`` in place, so no copy of the layer is made,
lengths: (B,) int32 — valid cache length per sequence (ragged batch), held
in SMEM through scalar prefetch and read as a scalar per program; ``layer``
rides beside it and is used only by the K/V index maps.

Why sequence-minor: a TPU tiles the two minor dims of an array by (8, 128).
A (S, D) page with D = 80 would pad D to 128 lanes, so XLA keeps such an
array transposed in HBM and any kernel reading (S, D) blocks forces a
relayout copy of the whole layer. (D, S) is dense as it stands.

Pages are ``block_k`` positions, or all of S when it is shorter. Where
``block_k`` does not divide S the last page runs past the cache's end: the
DMA reads only what lies inside, the rest of the block is undefined, so the
kernel masks V there as it masks the scores. The cache is never padded.

Grid: (B, Hkv, pages); per-page blocks are skipped entirely once past the
sequence length (`pl.when`), so short sequences in a ragged batch cost only
their own pages — straggler mitigation at the kernel level.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, interpret_default


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                   m_ref, l_ref, *, nkv: int, bk: int, scale: float,
                   softcap: Optional[float], window: Optional[int],
                   seq_minor: bool, ragged: bool):
    ik = pl.program_id(2)
    length = len_ref[pl.program_id(0)]
    start = jnp.maximum(length - window, 0) if window is not None else 0

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jnp.logical_and(ik * bk < length, (ik + 1) * bk > start))
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)   # (bk, d), (d, bk) if seq_minor
        v = v_ref[0, 0].astype(jnp.float32)
        kd = 0 if seq_minor else 1                       # k's d axis
        if ragged:      # the last page holds undefined data past the cache
            pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape,
                                                      1 - kd)
            v = jnp.where(pos < length, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (kd,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = jnp.logical_and(cols < length, cols >= start)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (1 - kd,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    *,
    layer: Optional[jax.Array] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """q: (B, Hkv, G, D); k, v: (B, Hkv, S, D), or (L, B, Hkv, D, S) with a
    scalar ``layer``; lengths: (B,) → (B, Hkv, G, D)."""
    if interpret is None:
        interpret = interpret_default()
    seq_minor = k.ndim == 5
    if not seq_minor:
        # one layer: a leading axis of 1 is a bitcast, not a copy
        k, v, layer = k[None], v[None], 0
    b, hkv, g, d = q.shape
    s = k.shape[4] if seq_minor else k.shape[3]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    # a page is a block's lane dim when the sequence is minor, else sublanes
    align = 128 if seq_minor else 8
    if block_k % align:
        raise ValueError(f"block_k must be a multiple of {align}")
    bk = min(block_k, s)
    nkv = pl.cdiv(s, bk)

    kernel = functools.partial(_decode_kernel, nkv=nkv, bk=bk, scale=scale,
                               softcap=softcap, window=window,
                               seq_minor=seq_minor, ragged=s % bk != 0)
    # index maps take the prefetched lengths and layer as trailing arguments
    if seq_minor:
        kv_spec = pl.BlockSpec((None, 1, 1, d, bk),
                               lambda bb, h, ik, ln, ly: (ly[0], bb, h, 0, ik))
    else:
        kv_spec = pl.BlockSpec((None, 1, 1, bk, d),
                               lambda bb, h, ik, ln, ly: (ly[0], bb, h, ik, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, h, ik, ln, ly: (bb, h, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bb, h, ik, ln, ly: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.reshape(b).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k, v)
