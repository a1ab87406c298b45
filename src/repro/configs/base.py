"""Architecture configuration schema.

One frozen dataclass describes every assigned architecture; per-arch modules
in this package instantiate it with the exact published numbers plus a
``smoke()`` reduction of the same family for CPU tests.

``pattern`` is the repeating layer period (MaxText-style scan over periods
keeps the HLO size independent of depth): e.g. gemma2 is ("attn_local",
"attn"); jamba's period of 8 holds one attention layer per seven Mamba layers
with MoE on odd positions.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed experts of an MoE FFN.

    ``n_experts`` is the router's width. ``router`` is ``softmax`` (top-k
    of the softmax, gates renormalised over the k) or ``sigmoid``
    (DeepSeek-V3's noaux_tc: top-k of ``sigmoid(logits) + bias``, the bias
    steering the choice only; gates are the unbiased scores renormalised
    over the k). Gates are then scaled by ``routed_scale``. ``d_expert``
    is each expert's width (0: ``d_ff``); ``n_shared`` shared experts of
    that width run on every token as one FFN. ``held`` is the range
    ``[start, stop)`` of experts this device computes (None: all); the
    others' share of the output is left out, as expert parallelism on one
    of several chips leaves it to the others.
    """
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    d_expert: int = 0
    n_shared: int = 0
    router: str = "softmax"
    routed_scale: float = 1.0
    held: Optional[tuple[int, int]] = None

    @property
    def held_range(self) -> tuple[int, int]:
        return self.held or (0, self.n_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held_range
        return hi - lo


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: Optional[int] = 768   # None: a direct q projection
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0          # 0 → ceil(d_model / 16)
    chunk: int = 128          # scan chunk for the selective scan


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64
    decay_lora: int = 64
    chunk: int = 64


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One position in the repeating layer pattern."""

    kind: str = "attn"            # attn | attn_local | mla | mamba | rwkv
    moe: bool = False             # MoE FFN at this position?


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | audio | vlm | hybrid | ssm
    n_layers: int                 # every layer, leading dense ones included
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0             # 0 → d_model // n_heads
    pattern: tuple[LayerSpec, ...] = (LayerSpec(),)
    # layers before the pattern's periods: the pattern's first mixer with a
    # dense FFN (DeepSeek-V3's first_k_dense_replace)
    n_dense_layers: int = 0
    # --- attention options
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0    # stablelm partial rotary
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    local_window: Optional[int] = None
    # serving: local(sliding-window) layers keep only a window-sized ring
    # cache instead of the full sequence (§Perf iteration 5)
    ring_local_cache: bool = False
    # --- submodule configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None
    # --- encoder/decoder (whisper)
    enc_dec: bool = False
    n_enc_layers: int = 0
    # --- vlm stub
    vision_prefix: int = 0        # number of precomputed patch embeddings
    audio_frontend: bool = False  # input is precomputed frame embeddings
    # --- misc
    act: str = "silu"             # silu | gelu
    norm: str = "rmsnorm"         # rmsnorm | layernorm
    tie_embeddings: bool = True
    embed_scale: bool = False     # gemma-style sqrt(d_model) embedding scale
    max_seq_len: int = 524_288
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    # ------------------------------------------------------------- derived
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def n_periods(self) -> int:
        n = self.n_layers - self.n_dense_layers
        assert n % self.period == 0, (self.n_layers, self.period)
        return n // self.period

    @property
    def dense_spec(self) -> LayerSpec:
        """The leading dense layers' spec."""
        return LayerSpec(kind=self.pattern[0].kind)

    @property
    def cache_specs(self) -> tuple[LayerSpec, ...]:
        """The spec of each entry of ``LM.init_cache``'s tuple: one per
        pattern position, then the leading dense layers', where any."""
        return self.pattern + ((self.dense_spec,) if self.n_dense_layers
                               else ())

    @property
    def expert_ff(self) -> int:
        return self.moe.d_expert or self.d_ff

    @property
    def pdtype(self):
        import jax.numpy as jnp   # deferred: shape-only users stay jax-free
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        import jax.numpy as jnp
        return jnp.dtype(self.compute_dtype)

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run 500k-token decode? SSM/hybrid: yes (attention
        layers in hybrids keep a full KV cache; pure full-attention: no)."""
        return all(s.kind in ("mamba", "rwkv") for s in self.pattern) or \
            any(s.kind in ("mamba", "rwkv") for s in self.pattern)

    def _layer_params(self, spec: LayerSpec, *, active: bool) -> int:
        """Parameters of one layer (norms aside); ``active``: only those a
        token uses, so top_k of the routed experts."""
        d, ff = self.d_model, self.d_ff
        hd = self.resolved_head_dim
        total = 0
        if spec.kind in ("attn", "attn_local"):
            qkv = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd)
            total += qkv + self.n_heads * hd * d
        elif spec.kind == "mla":
            m = self.mla
            q = self.n_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
            total += (d * q if m.q_lora_rank is None
                      else d * m.q_lora_rank + m.q_lora_rank * q)
            total += (d * (m.kv_lora_rank + m.qk_rope_head_dim)
                      + m.kv_lora_rank * self.n_heads *
                      (m.qk_nope_head_dim + m.v_head_dim)
                      + self.n_heads * m.v_head_dim * d)
        elif spec.kind == "mamba":
            mb = self.mamba
            di = mb.expand * d
            dtr = mb.dt_rank or -(-d // 16)
            total += (d * 2 * di + di * mb.d_conv
                      + di * (dtr + 2 * mb.d_state) + dtr * di
                      + di * mb.d_state + di + di * d)
        elif spec.kind == "rwkv":
            total += 4 * d * d + d * d + 2 * d * self.rwkv.decay_lora
            return total + 2 * d * ff          # rwkv channel-mix (2 mats)
        if spec.moe and self.moe is not None:
            mo = self.moe
            routed = mo.top_k if active else mo.n_experts
            total += d * mo.n_experts + (mo.n_experts if mo.router == "sigmoid"
                                         else 0)
            total += (routed + mo.n_shared) * 3 * d * self.expert_ff
        else:
            total += 3 * d * ff
        return total

    def _count(self, *, active: bool) -> int:
        d, ff, v = self.d_model, self.d_ff, self.vocab
        total = v * d  # embedding
        if not self.tie_embeddings:
            total += v * d
        total += self.n_dense_layers * self._layer_params(self.dense_spec,
                                                          active=active)
        total += self.n_periods * sum(self._layer_params(s, active=active)
                                      for s in self.pattern)
        if self.enc_dec:
            # encoder blocks + cross attention in decoder
            qkv = 4 * d * (self.n_heads * self.resolved_head_dim)
            total += self.n_enc_layers * (qkv + 3 * d * ff)
            total += self.n_layers * qkv  # cross-attn in each decoder layer
        return total

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks), for 6ND: every
        routed expert of the router, held here or not, and the shared ones."""
        return self._count(active=False)

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of n_experts, and the shared
        experts)."""
        return self._count(active=True)
