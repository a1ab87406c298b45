"""Plain float32 reference of the served decoder, and its int8 control.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, with no kernels, cache or
batching, written from the equations of the configuration as run (the
``model`` block of ``chipbench/configs/<config>.json``); it imports nothing
of the program. It reads the benchmark's own seeded weights
(``cbench.weights``) in their served layout and upcasts one layer at a time,
so a full-width model fits beside them.

Equations (``x`` the residual stream, one sequence, positions 0..S-1):

* embed: ``x = table[tokens]``;
* ``attn`` layer: ``h = norm1(x)``; ``q, k, v = h Wq, h Wk, h Wv``; rotary
  on the first ``rope_fraction`` of each head's channels, pairs
  ``(2i, 2i+1)``, frequencies ``theta^(-2i/rot)``; causal softmax of
  ``q k / sqrt(head_dim)``; ``x += (p v) Wo``;
* ``mla`` layer (MiniCPM3 / DeepSeek latent attention):
  ``q = rms(h Wq_down) Wq_up`` split per head into nope and rope parts;
  ``[c, k_rope] = h Wkv_down``, ``c = rms(c)``; ``k_nope = c K_up``,
  ``v = c V_up``; rotary on the rope parts (``k_rope`` shared by heads);
  scores over ``qk_nope + qk_rope`` channels scaled by
  ``1/sqrt(qk_nope + qk_rope)``; ``x += (p v) Wo``;
* FFN: ``h = norm2(x)``; ``x += (silu(h Wg) * (h Wu)) Wd``;
* final norm, then ``logits = x table_out^T``.

A configuration with other layers brings its own equations
(``chipbench/equations/<config>.py``), which may reuse these parts and
pass ``logits_at`` a ``block`` of its own, and its own ``settled_at``
where its reference makes a discrete choice (a router's top-k).

Norms: ``layernorm`` is ``(x - mean)/sqrt(var + 1e-5) * scale + bias``;
``rmsnorm`` is ``x/sqrt(mean(x^2) + 1e-6) * (1 + scale)``.

The control (``quant=True``) is the same computation with every
projection in int8 (W8A8): activations quantized per token and weights per
output channel, symmetric, dequantized into the float32 matmul. It is the
nearest precision below the configuration's bfloat16, and the step a
faster path would be tempted to take.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 256          # query rows per attention block


def _quant(x, axis):
    """Symmetric int8 round trip along ``axis`` (the contracted one)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(a, w, quant: bool):
    """(..., k) @ (k, n) in float32."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        a, w = _quant(a, -1), _quant(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _up(c, w, quant: bool):
    """(S, r) x (H, r, d) -> (S, H, d): the latent up-projections."""
    c = c.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        c, w = _quant(c, -1), _quant(w, 1)
    return jnp.einsum("sr,hrd->shd", c, w, precision=HI)


def _norm(kind, p, x):
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return ((x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32))
    if kind == "rmsnorm":
        var = (x * x).mean(-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + p["scale"].astype(jnp.float32))
    raise ValueError(f"reference has no norm {kind!r}")


def _rope(x, theta: float, fraction: float = 1.0):
    """x: (S, H, D); rotates the first ``fraction`` of D in (2i, 2i+1) pairs."""
    s, _, d = x.shape
    rot = int(d * fraction) // 2 * 2
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.reshape(s, x.shape[1], rot), x[..., rot:]], -1)


def _attend(q, k, v, scale: float):
    """Causal softmax attention, float32, in blocks of query rows.
    q, k: (S, H, Dk); v: (S, H, Dv) -> (S, H, Dv)."""
    s = q.shape[0]
    cols = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_CHUNK, Q_CHUNK, 0)
        sc = jnp.einsum("chd,shd->hcs", qb, k, precision=HI) * scale
        rows = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        sc = jnp.where(cols[None, None, :] <= rows[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hcs,shd->chd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(s // Q_CHUNK))
    return out.reshape(s, *out.shape[2:])


def _attn_layer(m, p, h, quant):
    s = h.shape[0]
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    q = _mm(h, p["q"]["w"], quant).reshape(s, m["n_heads"], hd)
    k = _mm(h, p["k"]["w"], quant).reshape(s, m["n_kv_heads"], hd)
    v = _mm(h, p["v"]["w"], quant).reshape(s, m["n_kv_heads"], hd)
    q = _rope(q, m["rope_theta"], m["rope_fraction"])
    k = _rope(k, m["rope_theta"], m["rope_fraction"])
    g = m["n_heads"] // m["n_kv_heads"]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    o = _attend(q, k, v, 1.0 / math.sqrt(hd))
    return _mm(o.reshape(s, -1), p["o"]["w"], quant)


def _mla_layer(m, p, h, quant):
    a = m["mla"]
    s, nh = h.shape[0], m["n_heads"]
    nope, rope, r = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["kv_lora_rank"]
    q_lat = _norm("rmsnorm", p["q_norm"], _mm(h, p["q_down"]["w"], quant))
    q = _mm(q_lat, p["q_up"]["w"], quant).reshape(s, nh, nope + rope)
    kv = _mm(h, p["kv_down"]["w"], quant)
    c = _norm("rmsnorm", p["kv_norm"], kv[:, :r])
    k_rope = _rope(kv[:, None, r:], m["rope_theta"])
    q_rope = _rope(q[..., nope:], m["rope_theta"])
    k_nope = _up(c, p["k_up"], quant)
    v = _up(c, p["v_up"], quant)
    qf = jnp.concatenate([q[..., :nope], q_rope], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (s, nh, rope))], -1)
    o = _attend(qf, kf, v, 1.0 / math.sqrt(nope + rope))
    return _mm(o.reshape(s, -1), p["o"]["w"], quant)


def _attention(m, kind, p, h, quant):
    """The attention sublayer of a layer of ``kind``, without its residual."""
    if kind == "attn":
        return _attn_layer(m, p, h, quant)
    if kind == "mla":
        return _mla_layer(m, p, h, quant)
    raise ValueError(f"reference has no layer kind {kind!r}")


def _swiglu(f, h, quant):
    """The dense FFN, without its residual."""
    act = jax.nn.silu(_mm(h, f["gate"]["w"], quant)) * _mm(h, f["up"]["w"], quant)
    return _mm(act, f["down"]["w"], quant)


def _block(m, spec, p, x, quant):
    """One layer; ``spec`` is its entry of ``m["pattern"]``."""
    if spec.get("moe"):
        raise ValueError("reference models dense FFNs only")
    h = _norm(m["norm"], p["ln1"], x)
    x = x + _attention(m, spec["kind"], p["attn"], h, quant)
    return x + _swiglu(p["ffn"], _norm(m["norm"], p["ln2"], x), quant)


def check_supported(m: dict) -> None:
    for key in ("attn_softcap", "final_softcap", "local_window"):
        if m.get(key):
            raise ValueError(f"reference does not model {key}")
    if m.get("act", "silu") != "silu" or m.get("qkv_bias") or m.get("embed_scale"):
        raise ValueError("reference models silu, no qkv bias, no embed scale")


@functools.lru_cache(maxsize=None)
def _programs(key: str, quant: bool, block):
    m = json.loads(key)
    specs = m["pattern"]

    def hidden(params, tokens):
        """tokens (S,) -> final-normed hidden states (S, d), float32."""
        x = params["embed"]["table"][tokens].astype(jnp.float32)

        def period(x, bps):
            for spec, bp in zip(specs, bps):
                x = block(m, spec, bp, x, quant)
            return x, None

        x, _ = jax.lax.scan(period, x, params["blocks"])
        return _norm(m["norm"], params["final_norm"], x)

    def logits(params, h):
        table = params["unembed" if "unembed" in params else "embed"]["table"]
        return _mm(h, table.T, quant)

    return jax.jit(hidden), jax.jit(logits)


def _freeze(m: dict) -> str:
    """A hashable key that holds the whole model block, nested dicts and
    lists included."""
    return json.dumps(m, sort_keys=True)


def logits_at(model: dict, params, tokens, rows, *, quant: bool = False,
              block=None):
    """Logits (len(rows), V) at positions ``rows`` of the sequence
    ``tokens``, already padded to a multiple of ``Q_CHUNK``: padding sits
    at the end, where causality keeps it from every earlier position.
    ``block(m, spec, params, x, quant)`` computes one layer (``_block``
    where none is given)."""
    check_supported(model)
    hidden, logits = _programs(_freeze(model), quant, block or _block)
    h = hidden(params, jnp.asarray(tokens, jnp.int32))
    return logits(params, h[jnp.asarray(rows, jnp.int32)])


def settled_at(model: dict, params, tokens, rows):
    """Every row: these layers make no discrete choice on the way to the
    logits, so rounding cannot swap one (an equations module whose layers
    route tokens marks the rows where it could)."""
    return np.ones(len(rows), bool)
