"""ArcaneEngine — trace-time software decode of the xmnmc ISA (production path).

The simulator (`core.runtime`) interprets instructions against the cache model;
models can't afford a Python interpreter per training step. The engine keeps
the paper's *mechanism* — complex instructions, software decode through the
kernel-library registry, renamed dependency dispatch — but applies it when the
step function is **traced**: every model-level matrix operation

  1. is *encoded* as a genuine xmnmc instruction word (bit-exact, the same
     encoder the simulator uses),
  2. is *software-decoded* through a ``KernelLibrary``-style registry that maps
     func5 → executor (Pallas micro-program on TPU, blocked-jnp reference
     elsewhere),
  3. lands in the traced program as one fused kernel invocation, with the
     instruction word retained in the engine's trace log (the "micro-program"
     the eCPU would have run).

Because XLA's dataflow + donation replace the AT/lock machinery at runtime,
what survives of §III is the *discipline*: fused VMEM-resident kernels and
WAR/WAW-free operand versioning (functional arrays are renamed by
construction — the paper's renaming applied at the IR level).

Width suffixes are extended to float dtypes (the ISA is software-defined —
reprogramming the decoder is the point): .w ↦ f32/i32, .h ↦ bf16/i16, .b ↦ i8.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.encoding import ElemWidth, encode_xmk
from repro.core.isa import fx_encode
from repro import kernels


def _width_of(dtype) -> ElemWidth:
    dt = jnp.dtype(dtype)
    if dt.itemsize >= 4:
        return ElemWidth.W
    if dt.itemsize == 2:
        return ElemWidth.H
    return ElemWidth.B


@dataclasses.dataclass(frozen=True)
class TraceEntry:
    word: int            # encoded xmnmc instruction
    mnemonic: str
    shapes: tuple
    flops: int


class ArcaneEngine:
    """Dispatch facade used by every model layer.

    backend: "pallas"  — Pallas kernels (Mosaic on a TPU),
             "ref"     — blocked-jnp reference path (pjit-partitionable; used
                         by the multi-pod dry-run and as the oracle).
    interpret: run the Pallas kernels in interpret mode. ``None`` decides
               from JAX's backend (interpret off a TPU, for the CPU tests);
               ``False`` lowers them for a TPU from any host, which is how
               the compile tests target a described chip.
    """

    def __init__(self, backend: str, *, attn_block_q: int = 256,
                 attn_block_k: int = 256, gemm_block: tuple = (128, 128, 128),
                 interpret: Optional[bool] = None, record: bool = False):
        if backend not in ("pallas", "ref"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self.attn_block_q = attn_block_q
        self.attn_block_k = attn_block_k
        self.gemm_block = gemm_block
        self.interpret = interpret
        self.record = record
        self.trace: list[TraceEntry] = []
        # attention backend name differs: blocked-jnp ref is "chunked"
        self._attn_backend = "pallas" if backend == "pallas" else "chunked"

    # ------------------------------------------------------------- recording
    def _log(self, func5: int, dtype, shapes, flops: int, **kw) -> None:
        if not self.record:
            return
        off = encode_xmk(func5, _width_of(dtype), md=0, **kw)
        self.trace.append(TraceEntry(word=off.word, mnemonic=off.instr.mnemonic,
                                     shapes=tuple(shapes), flops=flops))

    # ------------------------------------------------------------------ ops
    def gemm(self, x: jax.Array, w: jax.Array, c: Optional[jax.Array] = None,
             *, alpha: float = 1.0, beta: float = 1.0,
             out_dtype=None) -> jax.Array:
        """xmk0 over arbitrary leading dims: (..., k) @ (k, n) [+ beta*c]."""
        lead = x.shape[:-1]
        k = x.shape[-1]
        n = w.shape[-1]
        m = 1
        for s in lead:
            m *= s
        self._log(0, x.dtype, (x.shape, w.shape), 2 * m * k * n,
                  alpha=fx_encode(min(max(alpha, -127), 127)),
                  beta=fx_encode(min(max(beta, -127), 127)))
        x2 = x.reshape(m, k)
        c2 = c.reshape(m, n) if c is not None else None
        if self.backend == "ref":
            out = jnp.dot(x2, w, preferred_element_type=jnp.float32)
            if alpha != 1.0:
                out = alpha * out
            if c2 is not None:
                out = out + beta * c2.astype(out.dtype)
            out = out.astype(out_dtype or x.dtype)
        else:
            bm, bn, bk = self.gemm_block
            out = kernels.gemm(x2, w, c2, alpha=alpha, beta=beta,
                               block_m=bm, block_n=bn, block_k=bk,
                               out_dtype=out_dtype or x.dtype,
                               interpret=self.interpret)
        return out.reshape(*lead, n)

    def leakyrelu(self, x: jax.Array, *, negative_slope: float = 0.01) -> jax.Array:
        self._log(1, x.dtype, (x.shape,), int(x.size))
        if self.backend == "ref":
            return jnp.where(x >= 0, x, negative_slope * x)
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        return kernels.leakyrelu(x2, negative_slope=negative_slope,
                                 interpret=self.interpret).reshape(shape)

    def maxpool(self, x: jax.Array, *, win: int = 2,
                stride: Optional[int] = None) -> jax.Array:
        self._log(2, x.dtype, (x.shape,), int(x.size))
        if self.backend == "ref":
            from repro.kernels.maxpool.ref import maxpool_ref
            return maxpool_ref(x, win=win, stride=stride)
        return kernels.maxpool(x, win=win, stride=stride,
                               interpret=self.interpret)

    def conv_layer(self, x: jax.Array, f: jax.Array, *,
                   negative_slope: float = 0.0) -> jax.Array:
        cch, h, w = x.shape
        nf, _, kh, kw = f.shape
        self._log(4, x.dtype, (x.shape, f.shape),
                  2 * nf * cch * (h - kh + 1) * (w - kw + 1) * kh * kw)
        return kernels.conv_layer(x, f, negative_slope=negative_slope,
                                  backend=self.backend,
                                  interpret=self.interpret)

    def attention(self, q, k, v, *, causal=True, window=None, softcap=None,
                  scale=None, kv_len=None) -> jax.Array:
        b, hq, sq, d = q.shape
        skv = k.shape[2]
        self._log(5, q.dtype, (q.shape, k.shape), 4 * b * hq * sq * skv * d)
        return kernels.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, kv_len=kv_len, block_q=self.attn_block_q,
            block_k=self.attn_block_k, backend=self._attn_backend,
            interpret=self.interpret)

    def decode_attention(self, q, k, v, lengths, *, softcap=None,
                         scale=None, window=None) -> jax.Array:
        b, hq, d = q.shape
        s = k.shape[2]
        self._log(6, q.dtype, (q.shape, k.shape), 4 * b * hq * s * d)
        return kernels.decode_attention(q, k, v, lengths, softcap=softcap,
                                        scale=scale, window=window,
                                        block_k=self.attn_block_k,
                                        backend=self.backend,
                                        interpret=self.interpret)

