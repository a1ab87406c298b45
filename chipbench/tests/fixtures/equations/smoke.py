"""Equations of the MoE smoke configuration, as a configuration brings its
own (``chipbench/equations/<config>.py``); the tests copy this file there.

Every layer is ``attn`` with a routed MoE FFN in place of the dense one
(``pattern: [{"kind": "attn", "moe": true}]``). The FFN, per token ``h``:
``p = softmax(h Wr)`` over ``n_experts``; the ``top_k`` largest, their
gates ``p_i / sum p_i``; ``out = sum_i g_i (silu(h Wg_i) * (h Wu_i)) Wd_i``.
Every token reaches its experts: the program drops none while
``capacity_factor >= n_experts / top_k``. The control quantizes each
projection, the router and the experts too, as ``cbench.reference`` does.

Settled rows: the choice of experts is discrete, so where rounding the
router's input ``h`` at the configuration's compute precision (unit
roundoff ``u``) could swap a chosen expert ``i`` for an unchosen ``j``,
that is ``l_i - l_j <= 4 u sqrt(sum_d (h_d (Wr_di - Wr_dj))^2)`` for the
router logits ``l`` in some layer (rounding errors add up as their root
sum of squares; the worst case, ``u sum_d |...|``, is ~sqrt(d) times
wider and leaves out every row at Moonlight's widths), the reference's
answer at that position is not the program's to match, and the check
leaves the row out. A swap still reaches later positions through
attention; their rows are kept.

Counts: the attention projections and the unembedding are GEMM kernel
calls; router and experts are XLA einsums, in the model FLOPs only (top_k
experts a token).
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from cbench import reference as ref
from cbench.counts import (F32, decode_attention_calls,  # noqa: F401
                           flash_attention_calls, gemm_cost, layer_gemms)


def _experts(a, w, quant):
    """(E, S, k) x (E, k, n) -> (E, S, n) in float32."""
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if quant:
        a, w = ref._quant(a, -1), ref._quant(w, 1)
    return jnp.einsum("esk,ekn->esn", a, w, precision=ref.HI)


def _moe(m, f, h, quant):
    e, k = m["moe"]["n_experts"], m["moe"]["top_k"]
    probs = jax.nn.softmax(ref._mm(h, f["router"]["w"], quant), -1)
    top, ids = jax.lax.top_k(probs, k)
    gates = (jax.nn.one_hot(ids, e) * (top / top.sum(-1, keepdims=True))[..., None]
             ).sum(1)                                           # (S, E)
    he = jnp.broadcast_to(h, (e, *h.shape))      # every expert over every token
    act = jax.nn.silu(_experts(he, f["gate"], quant)) * _experts(he, f["up"], quant)
    return jnp.einsum("se,esd->sd", gates, _experts(act, f["down"], quant),
                      precision=ref.HI)


# unit roundoff of the router's input at the compute precision
ROUNDING = {"bfloat16": 2.0 ** -8, "float32": 2.0 ** -24}


def _unsettled(m, f, h):
    """Per token: whether rounding could swap a chosen expert for another."""
    w = f["router"]["w"].astype(jnp.float32)
    logits = jnp.matmul(h, w, precision=ref.HI)                      # (S, E)
    _, ids = jax.lax.top_k(logits, m["moe"]["top_k"])
    chosen = jax.nn.one_hot(ids, w.shape[1]).sum(1) > 0             # (S, E)
    margin = logits[:, :, None] - logits[:, None, :]                 # (S, i, j)
    dw = w[:, :, None] - w[:, None, :]
    spread = jnp.sqrt(jnp.einsum("sd,dij->sij", h * h, dw * dw, precision=ref.HI))
    u = ROUNDING[m.get("compute_dtype", "bfloat16")]
    near = (margin <= 4 * u * spread) & chosen[:, :, None] & ~chosen[:, None, :]
    return near.any((1, 2))


def _layer(m, spec, p, x, quant):
    """One layer -> (x, per token whether its routing is unsettled)."""
    h = ref._norm(m["norm"], p["ln1"], x)
    x = x + ref._attention(m, spec["kind"], p["attn"], h, quant)
    h = ref._norm(m["norm"], p["ln2"], x)
    return x + _moe(m, p["ffn"], h, quant), _unsettled(m, p["ffn"], h)


def _block(m, spec, p, x, quant):
    return _layer(m, spec, p, x, quant)[0]


def logits_at(model, params, tokens, rows, *, quant=False):
    return ref.logits_at(model, params, tokens, rows, quant=quant, block=_block)


@functools.lru_cache(maxsize=None)
def _unsettled_program(key: str):
    m = json.loads(key)

    def run(params, tokens):
        def period(x, bps):
            unsettled = jnp.zeros(x.shape[0], bool)
            for spec, bp in zip(m["pattern"], bps):
                x, near = _layer(m, spec, bp, x, False)
                unsettled = unsettled | near
            return x, unsettled
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        return jax.lax.scan(period, x, params["blocks"])[1].any(0)
    return jax.jit(run)


def settled_at(model, params, tokens, rows):
    run = _unsettled_program(ref._freeze(model))
    return ~np.asarray(run(params, jnp.asarray(tokens, jnp.int32)))[np.asarray(rows)]


def _attn_gemms(m, rows):
    return layer_gemms(m, "attn", rows)[:4]     # q, k, v, o


def gemm_calls(m, rows, logit_rows):
    calls = [gemm_cost(*c) for _ in range(m["n_layers"]) for c in _attn_gemms(m, rows)]
    return calls + [gemm_cost(logit_rows, m["d_model"], m["vocab"], F32)]


def _layer_flops(m):
    """Matmul FLOPs per token of one layer: projections, router, experts."""
    d, moe = m["d_model"], m["moe"]
    return (sum(2 * k * n for _, k, n in _attn_gemms(m, 1)) + 2 * d * moe["n_experts"]
            + moe["top_k"] * 3 * 2 * d * m["d_ff"])


def model_flops_decode(m, length):
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    per_layer = _layer_flops(m) + 4 * length * m["n_heads"] * hd
    return m["n_layers"] * per_layer + 2 * m["d_model"] * m["vocab"]


def model_flops_prefill(m, s):
    attn = sum(fl for fl, _ in flash_attention_calls(m, s))
    return m["n_layers"] * s * _layer_flops(m) + attn + 2 * m["d_model"] * m["vocab"]
