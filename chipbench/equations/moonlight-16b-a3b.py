"""Equations of Moonlight-16B-A3B as this configuration serves it: one chip
of a deployment that spreads each layer's 64 routed experts over 8 chips,
so this chip holds experts ``held`` (0-7) of every MoE layer.

Layers (``x`` the residual stream, one sequence, float32):

* layer 0 (``dense_blocks``): MLA, then the dense FFN of width ``d_ff``;
* layers 1-26 (``blocks``): MLA, then the MoE FFN;
* MLA, with a direct q projection (``q_lora_rank`` null): ``h = rms1(x)``;
  ``q = h Wq`` split per head into nope and rope parts; ``[c, k_rope] =
  h Wkv_down``, ``c = rms(c)``; ``k_nope = c K_up``, ``v = c V_up``;
  rotary on the rope parts (``k_rope`` shared by the heads), pairs
  ``(2i, 2i+1)``; causal softmax over ``nope + rope`` channels scaled by
  ``1/sqrt(nope + rope)``; ``x += (p v) Wo``;
* MoE FFN, per token ``h = rms2(x)``: scores ``s = sigmoid(h Wr)`` over
  all 64 experts; the chosen ``C`` are the top 6 of ``s + bias`` (the bias
  steers the choice only, noaux_tc); gates ``g_i = 2.446 s_i / sum_C s``;
  ``x += sum_{i in C, held} g_i E_i(h) + E_shared(h)``, each ``E`` a SwiGLU
  ``(silu(h Wg) * (h Wu)) Wd`` (the shared experts one of width 2 x 1408).
  A chosen expert held on another chip adds nothing here, in the program
  and in this reference alike;
* final rms, then ``logits = x Wout^T`` (untied).

The helpers are ``cbench.reference``'s (norms, rotary, attention, the
int8 control's quantizer); weights are upcast one layer at a time inside
the layer scans, so the reference fits beside the served weights.

Settled rows. The program computes ``h`` in bfloat16, so its router sees
``h + dh`` with ``dh_d`` of the order ``u |h_d|`` (u = 2^-8, the unit
roundoff of the model block's ``compute_dtype``), the errors
independent across ``d``. To first order the biased score of expert ``i``
moves by ``s_i (1 - s_i) sum_d dh_d Wr_di``, so the margin ``sel_i - sel_j``
(``sel = s + bias``) of a chosen ``i`` over an unchosen ``j`` moves by a
sum whose root mean square is

    u sqrt(sum_d h_d^2 (a_i Wr_di - a_j Wr_dj)^2),   a = s (1 - s),
    = u sqrt(a_i^2 Q_ii + a_j^2 Q_jj - 2 a_i a_j Q_ij),   Q = Wr^T diag(h^2) Wr.

A swap of ``i`` for ``j`` is within rounding where the margin is at most
``TAU`` times that (the MoE smoke fixture's 4u rule, in biased scores).
Such a swap changes what this chip computes only (a) where ``i`` or ``j``
is held: a held expert's whole output comes or goes; or (b) where a held
expert stays chosen and the normaliser ``sum_C s`` moves, by ``|s_i -
s_j|``, more than rounding moves it: ``|s_i - s_j| > u sum_C s``. A swap
between two experts held elsewhere with no held expert chosen moves
nothing here. A position where some layer has such a swap is unsettled,
and the check leaves its row out.

Counts. The GEMM kernel runs the MLA projections (q, kv_down, o), the
dense layer's FFN, the shared experts and the unembedding; the router, the
latent up-projections and MLA's absorbed decode are XLA ops, and the routed
experts run in the ``expert_gemm`` kernel (``kernel.expert_gemm.roofline``
prices those). The model FLOPs count the held experts' expected share of a
token's 6 routed experts: 6 x 8/64 = 0.75 experts a token. So a decode
token here costs 2.797 GFLOP before attention against 5.158 GFLOP for
the whole model (all 64 experts held, 2 x its 2.91B active parameters less
the embedding lookup): a ratio of 0.542 (the routed experts alone: 0.125).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from cbench import reference as ref
from cbench.counts import (F32, decode_attention_calls,  # noqa: F401
                           flash_attention_calls, gemm_cost)

# unit roundoff of the router's input at the compute precision
ROUNDING = {"bfloat16": 2.0 ** -8, "float32": 2.0 ** -24}
TAU = 4.0              # a margin within TAU rounding RMS's can swap


def _mla(m, p, h, quant):
    """MLA with a direct q projection, without its residual."""
    a = m["mla"]
    s, nh = h.shape[0], m["n_heads"]
    nope, rope, r = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["kv_lora_rank"]
    q = ref._mm(h, p["q"]["w"], quant).reshape(s, nh, nope + rope)
    kv = ref._mm(h, p["kv_down"]["w"], quant)
    c = ref._norm("rmsnorm", p["kv_norm"], kv[:, :r])
    k_rope = ref._rope(kv[:, None, r:], m["rope_theta"])
    q_rope = ref._rope(q[..., nope:], m["rope_theta"])
    k_nope = ref._up(c, p["k_up"], quant)
    v = ref._up(c, p["v_up"], quant)
    qf = jnp.concatenate([q[..., :nope], q_rope], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (s, nh, rope))], -1)
    o = ref._attend(qf, kf, v, 1.0 / math.sqrt(nope + rope))
    return ref._mm(o.reshape(s, -1), p["o"]["w"], quant)


def _experts(spec, a, w, quant):
    """Every held expert's projection of ``a`` in float32: ``spec`` is
    the einsum, ``w`` (E, k, n) contracted on its axis 1."""
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if quant:
        a, w = ref._quant(a, -1), ref._quant(w, 1)
    return jnp.einsum(spec, a, w, precision=ref.HI)


def _held(moe):
    lo, hi = moe.get("held") or (0, moe["n_experts"])
    return jnp.arange(moe["n_experts"]) - lo, lo, hi


def _moe(m, f, h, quant, flags):
    """The MoE FFN without its residual -> (out, per token whether
    rounding could swap an expert choice that matters here; with
    ``flags`` only, else None)."""
    moe = m["moe"]
    e, k = moe["n_experts"], moe["top_k"]
    local, lo, hi = _held(moe)
    held = (local >= 0) & (local < hi - lo)                            # (E,)
    s = jax.nn.sigmoid(ref._mm(h, f["router"]["w"], quant))            # (S, E)
    sel = s + f["router"]["bias"].astype(jnp.float32)
    _, ids = jax.lax.top_k(sel, k)
    chosen = jax.nn.one_hot(ids, e).sum(1) > 0                         # (S, E)
    total = jnp.sum(jnp.where(chosen, s, 0.0), -1, keepdims=True)
    gates = jnp.where(chosen & held, s, 0.0) / total * moe["routed_scale"]
    act = (jax.nn.silu(_experts("sk,ekn->esn", h, f["gate"], quant))
           * _experts("sk,ekn->esn", h, f["up"], quant))             # (E, S, ff)
    routed = jnp.einsum("se,esd->sd", gates[:, lo:hi],
                        _experts("esk,ekn->esn", act, f["down"], quant),
                        precision=ref.HI)
    out = routed + ref._swiglu(f["shared"], h, quant)
    u = ROUNDING[m.get("compute_dtype", "bfloat16")]
    return out, (_unsettled(f, h, s, sel, chosen, held, total, u) if flags
                 else None)


def _unsettled(f, h, s, sel, chosen, held, total, u):
    """Per token: whether a swap within rounding would change what this
    chip computes (the module docstring's rule)."""
    w = f["router"]["w"].astype(jnp.float32)
    q = jnp.einsum("sd,di,dj->sij", h * h, w, w, precision=ref.HI)  # (S, E, E)
    a = s * (1.0 - s)
    qd = jnp.diagonal(q, axis1=1, axis2=2)
    var = ((a * a * qd)[:, :, None] + (a * a * qd)[:, None, :]
           - 2.0 * a[:, :, None] * a[:, None, :] * q)
    near = (sel[:, :, None] - sel[:, None, :]
            <= TAU * u * jnp.sqrt(jnp.maximum(var, 0.0)))
    near = near & chosen[:, :, None] & ~chosen[:, None, :]            # i in C, j not
    any_held = jnp.any(chosen & held, -1)[:, None, None]
    moves = jnp.abs(s[:, :, None] - s[:, None, :]) > u * total[:, :, None]
    matters = held[None, :, None] | held[None, None, :] | (any_held & moves)
    return jnp.any(near & matters, (1, 2))


def _layer(m, p, x, quant, moe: bool, flags: bool = False):
    """One layer -> (x, per token whether its routing is unsettled, with
    ``flags``)."""
    x = x + _mla(m, p["attn"], ref._norm(m["norm"], p["ln1"], x), quant)
    h = ref._norm(m["norm"], p["ln2"], x)
    if not moe:
        return x + ref._swiglu(p["ffn"], h, quant), None
    out, unsettled = _moe(m, p["ffn"], h, quant, flags)
    return x + out, unsettled


@functools.lru_cache(maxsize=None)
def _programs(key: str, quant: bool, flags: bool = False):
    m = json.loads(key)

    def hidden(params, tokens):
        """tokens (S,) -> (final-normed hidden states (S, d), and with
        ``flags`` per token whether some layer's routing is unsettled)."""
        x = params["embed"]["table"][tokens].astype(jnp.float32)

        def dense(x, bp):
            return _layer(m, bp, x, quant, False)[0], None

        def moe(x, bp):
            return _layer(m, bp[0], x, quant, True, flags)

        if m.get("n_dense_layers"):
            x, _ = jax.lax.scan(dense, x, params["dense_blocks"])
        x, unsettled = jax.lax.scan(moe, x, params["blocks"])
        return (ref._norm(m["norm"], params["final_norm"], x),
                unsettled.any(0) if flags else None)

    def logits(params, h):
        return ref._mm(h, params["unembed"]["table"].T, quant)

    return jax.jit(hidden), jax.jit(logits)


def _check(m):
    ref.check_supported(m)
    if m["pattern"] != [{"kind": "mla", "moe": True}] or m["mla"]["q_lora_rank"]:
        raise ValueError("these equations are MLA with direct q and MoE layers")
    if m["moe"]["router"] != "sigmoid" or m["tie_embeddings"]:
        raise ValueError("these equations are a sigmoid router and untied logits")


def logits_at(model, params, tokens, rows, *, quant=False):
    """Logits (len(rows), V) at positions ``rows`` of ``tokens`` (padded to
    a multiple of ``cbench.reference.Q_CHUNK``, padding at the end)."""
    _check(model)
    hidden, logits = _programs(ref._freeze(model), quant)
    h, _ = hidden(params, jnp.asarray(tokens, jnp.int32))
    return logits(params, h[jnp.asarray(rows, jnp.int32)])


def settled_at(model, params, tokens, rows):
    _check(model)
    hidden, _ = _programs(ref._freeze(model), False, True)
    _, unsettled = hidden(params, jnp.asarray(tokens, jnp.int32))
    return ~np.asarray(unsettled)[np.asarray(rows)]


# ------------------------------------------------------------------ counts
def _widths(m):
    a, moe = m["mla"], m["moe"]
    return (m["d_model"], m["n_heads"], a["qk_nope_head_dim"],
            a["qk_rope_head_dim"], a["v_head_dim"], a["kv_lora_rank"],
            moe["d_expert"] or m["d_ff"])


def _mla_gemms(m, rows):
    d, h, nope, rope, v, r, _ = _widths(m)
    return [(rows, d, h * (nope + rope)), (rows, d, r + rope), (rows, h * v, d)]


def _ffn_gemms(m, rows, ff):
    d = m["d_model"]
    return [(rows, d, ff), (rows, d, ff), (rows, ff, d)]


def _layer_gemms(m, rows):
    """(M, K, N) of every GEMM kernel call of the layers over ``rows``."""
    n_dense = m.get("n_dense_layers", 0)
    shared = m["moe"]["n_shared"] * _widths(m)[-1]
    out = []
    for i in range(m["n_layers"]):
        out += _mla_gemms(m, rows) + _ffn_gemms(m, rows, m["d_ff"] if i < n_dense
                                                else shared)
    return out


def gemm_calls(m, rows, logit_rows):
    calls = [gemm_cost(*c) for c in _layer_gemms(m, rows)]
    return calls + [gemm_cost(logit_rows, m["d_model"], m["vocab"], F32)]


def held_experts_per_token(m) -> float:
    """The held experts' expected share of a token's routed experts."""
    moe = m["moe"]
    _, lo, hi = _held(moe)
    return moe["top_k"] * (hi - lo) / moe["n_experts"]


def _token_flops(m):
    """Matmul FLOPs per token outside attention over the cache: the GEMM
    kernel's, MLA's latent up-projections (absorbed or expanded alike), the
    router and the held experts' expected share."""
    d, h, nope, _, v, r, ff = _widths(m)
    n_moe = m["n_layers"] - m.get("n_dense_layers", 0)
    total = sum(2 * k * n for _, k, n in _layer_gemms(m, 1))
    total += m["n_layers"] * 2 * h * r * (nope + v)
    total += n_moe * (2 * d * m["moe"]["n_experts"]
                      + held_experts_per_token(m) * 3 * 2 * d * ff)
    return total


def model_flops_decode(m, length):
    _, h, _, rope, _, r, _ = _widths(m)
    attn = m["n_layers"] * (2 * h * length * (r + rope) + 2 * h * length * r)
    return _token_flops(m) + attn + 2 * m["d_model"] * m["vocab"]


def model_flops_prefill(m, s):
    attn = sum(fl for fl, _ in flash_attention_calls(m, s))
    return s * _token_flops(m) + attn + 2 * m["d_model"] * m["vocab"]
