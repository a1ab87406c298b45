"""Top-k routed Mixture-of-Experts with shared experts and a held subset.

Router (``moe.router``, float32). ``softmax``: the top-k of the softmax,
gates renormalised over the k. ``sigmoid`` (DeepSeek-V3's noaux_tc):
scores ``s = sigmoid(h Wr)``, the top-k of ``s + bias`` (the bias steers the
choice only), gates ``s_i / sum_chosen s``. Both then scale by
``routed_scale``. Routing is over all ``n_experts``; this device computes
only its ``held`` experts' part, and a chosen expert held elsewhere adds
nothing here (on one chip of an expert-parallel deployment the others add
it). ``n_shared`` shared experts run on every token as one FFN
(``moe.shared``, through the GEMM kernel) and their output is added once.

Two dispatches of the routed part:

* **Dropless** (``dropless=True``: prefill and decode). ``moe.dispatch``
  sorts the (token, held expert) pairs by expert into row groups, each
  padded up to whole tiles of ``block_m`` rows; ``moe.experts`` runs the
  grouped ``expert_gemm`` kernel for gate, up and down, reading only the
  weights of experts that received rows; ``moe.combine`` gathers each
  pair's row back, weighted by its gate. No token is dropped, so serving
  matches the layer's equations whatever the routing. The rows each held
  expert received come back beside the output, so serving can report
  which experts' weights the layer read.
* **Capacity** (the training ``forward``): grouped, capacity-based
  dispatch (GShard). It stays for training because it partitions under
  SPMD: every tensor has a static shape that shards, tokens in groups that
  align with the data shards, every scatter/gather group-local, and the
  only cross-device exchange the (G ↔ E) transpose, the canonical MoE
  all-to-all, with experts sharded over the model axis. The dropless
  dispatch's group sizes are data, which no static sharding can split.
  Tokens beyond an expert's per-group capacity are dropped
  (``capacity_factor``); position-in-expert uses a stable argsort (the
  one-hot cumsum it replaced lowered to a quadratic prefix sum, §Perf
  iteration 1); the Switch/GShard load-balancing loss is returned.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.engine import ArcaneEngine
from repro.distributed.sharding import constrain
from repro.kernels.common import cdiv, round_up
from repro.models.layers import activation, dense_init, truncated_normal_init
from repro.models.mlp import mlp, mlp_init

# Target tokens per dispatch group; groups align with data shards.
GROUP_TOKENS = 8192


def moe_init(key, cfg: ModelConfig) -> dict:
    d, ff = cfg.d_model, cfg.expert_ff
    mcfg = cfg.moe
    e, held = mcfg.n_experts, mcfg.n_held
    dt = cfg.pdtype
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    scale = 1.0 / (d ** 0.5)
    router = dense_init(kr, d, e, jnp.float32)
    if mcfg.router == "sigmoid":
        router["bias"] = jnp.zeros((e,), jnp.float32)
    p = {
        "router": router,
        "gate": truncated_normal_init(kg, (held, d, ff), dt, scale),
        "up": truncated_normal_init(ku, (held, d, ff), dt, scale),
        "down": truncated_normal_init(kd, (held, ff, d), dt, 1.0 / (ff ** 0.5)),
    }
    if mcfg.n_shared:
        p["shared"] = mlp_init(ks, cfg, d_ff=mcfg.n_shared * ff)
    return p


def route(params: dict, cfg: ModelConfig, xt: jax.Array):
    """xt: (T, d) → (shares (T, E) for the balance loss, expert ids (T, k),
    gates (T, k)), all float32."""
    mcfg = cfg.moe
    logits = jnp.dot(xt.astype(jnp.float32), params["router"]["w"],
                     precision=jax.lax.Precision.HIGHEST)
    if mcfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + params["router"]["bias"], mcfg.top_k)
        gates = jnp.take_along_axis(scores, ids, axis=-1)
        shares = scores / jnp.sum(scores, axis=-1, keepdims=True)
    elif mcfg.router == "softmax":
        shares = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(shares, mcfg.top_k)
    else:
        raise ValueError(f"unknown router {mcfg.router!r}")
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return shares, ids, gates * mcfg.routed_scale


def _held_ids(cfg: ModelConfig, ids: jax.Array):
    """Expert ids → (index among the held experts, whether held)."""
    lo, _ = cfg.moe.held_range
    local = ids - lo
    return local, (local >= 0) & (local < cfg.moe.n_held)


def _block_rows(t: int, cfg: ModelConfig) -> int:
    """Rows per expert tile: the rows an expert expects (t·k/E), in
    multiples of 16 (a bfloat16 sublane tile), at most 128."""
    mcfg = cfg.moe
    return min(128, round_up(max(cdiv(t * mcfg.top_k, mcfg.n_experts), 1), 16))


def _dropless(engine: ArcaneEngine, params: dict, cfg: ModelConfig,
              xt: jax.Array, ids: jax.Array, gates: jax.Array):
    """The held experts' gated output, every pair computed: (T, d) f32,
    and the rows each held expert received, (n_held,) int32."""
    t, d = xt.shape
    k, g = ids.shape[1], cfg.moe.n_held
    bm = _block_rows(t, cfg)
    # every pair held, each group a tile short of full: the most rows
    n_rows = round_up(t * min(k, g) + g * (bm - 1), bm)
    with jax.named_scope("moe.dispatch"):
        local, held = _held_ids(cfg, ids.reshape(-1))
        key = jnp.where(held, local, g)                # not held: last
        sizes = jnp.sum(key[:, None] == jnp.arange(g), axis=0,
                        dtype=jnp.int32)              # (G,)
        padded = cdiv(sizes, bm) * bm
        starts = jnp.cumsum(padded) - padded          # group's first row
        order = jnp.argsort(key, stable=True)
        sorted_key = jnp.take(key, order)
        first = jnp.searchsorted(sorted_key, jnp.arange(g))
        grp = jnp.minimum(sorted_key, g - 1)
        row_sorted = jnp.where(
            sorted_key < g,
            jnp.take(starts, grp) + jnp.arange(t * k) - jnp.take(first, grp),
            n_rows)
        row = jnp.zeros((t * k,), jnp.int32).at[order].set(
            row_sorted.astype(jnp.int32))             # each pair's row
        token_of_row = jnp.full((n_rows,), t, jnp.int32).at[row].set(
            jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
        rows = jnp.take(jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)]),
                        token_of_row, axis=0)         # (R, d); padding 0
    with jax.named_scope("moe.experts"):
        act = activation(cfg.act)
        h = (act(engine.expert_gemm(rows, params["gate"], sizes, block_m=bm)
                 .astype(jnp.float32))
             * engine.expert_gemm(rows, params["up"], sizes, block_m=bm)
             .astype(jnp.float32)).astype(xt.dtype)
        y = engine.expert_gemm(h, params["down"], sizes, block_m=bm)
    with jax.named_scope("moe.combine"):
        # a pair held elsewhere has no row: it adds nothing (its row index
        # points past the groups, where the kernel leaves rows undefined)
        picked = jnp.take(y, jnp.minimum(row, n_rows - 1), axis=0)
        picked = jnp.where(held[:, None], picked.astype(jnp.float32), 0.0)
        out = jnp.sum((picked * gates.reshape(-1, 1)).reshape(t, k, d),
                      axis=1)
    return out, sizes


def _group_dispatch(xt, expert_ids, gate_vals, e: int, cap: int):
    """Group-local dispatch. xt: (S_g, d); ids/gates: (S_g, k), ids local
    to the held experts with ``e`` marking one held elsewhere.

    Returns (dispatched (E·cap, d), flat_idx (S_g·k,), keep, slot_gate).
    """
    k = expert_ids.shape[-1]
    s_g = xt.shape[0]
    slot_expert = expert_ids.reshape(-1)
    slot_gate = gate_vals.reshape(-1)
    n_slots = s_g * k
    order = jnp.argsort(slot_expert, stable=True)
    sorted_e = jnp.take(slot_expert, order)
    group_start = jnp.searchsorted(sorted_e, jnp.arange(e + 1))
    pos_sorted = jnp.arange(n_slots) - jnp.take(group_start, sorted_e)
    slot_pos = jnp.zeros((n_slots,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32))
    keep = (slot_pos < cap) & (slot_expert < e)
    flat_idx = jnp.where(keep, slot_expert * cap + slot_pos, e * cap)
    token_of_slot = jnp.repeat(jnp.arange(s_g), k)
    dispatched = jnp.zeros((e * cap + 1, xt.shape[1]), xt.dtype).at[
        flat_idx].set(jnp.take(xt, token_of_slot, axis=0), mode="drop")
    return dispatched[: e * cap], flat_idx, keep, slot_gate


def _group_combine(y, flat_idx, keep, slot_gate, k: int):
    """Inverse of _group_dispatch. y: (E·cap, d) → (S_g, d)."""
    e_cap = y.shape[0]
    gathered = jnp.where(
        keep[:, None], jnp.take(y, flat_idx.clip(0, e_cap - 1), axis=0), 0.0)
    weighted = gathered * slot_gate[:, None].astype(gathered.dtype)
    s_g = flat_idx.shape[0] // k
    return jnp.sum(weighted.reshape(s_g, k, -1), axis=1)


def _capacity(params: dict, cfg: ModelConfig, xt: jax.Array,
              ids: jax.Array, gates: jax.Array) -> jax.Array:
    """The held experts' gated output through GShard capacity dispatch."""
    t, d = xt.shape
    mcfg = cfg.moe
    k, e = mcfg.top_k, mcfg.n_held
    g = max(1, t // GROUP_TOKENS)
    while t % g:           # g must divide T; shrink to the nearest divisor
        g -= 1
    s_g = t // g
    cap = int(mcfg.capacity_factor * s_g * k / mcfg.n_experts) + 1
    local, held = _held_ids(cfg, ids)
    xg = xt.reshape(g, s_g, d)
    idsg = jnp.where(held, local, e).reshape(g, s_g, k)
    gatesg = gates.reshape(g, s_g, k)
    with jax.named_scope("moe.dispatch"):
        dispatched, flat_idx, keep, slot_gate = jax.vmap(
            lambda xx, ii, gg: _group_dispatch(xx, ii, gg, e, cap))(
                xg, idsg, gatesg)                   # (G, E·cap, d), ...
        # (G, E, cap, d) → (E, G·cap, d): the MoE all-to-all (data ↔ experts)
        xe = dispatched.reshape(g, e, cap, d).swapaxes(0, 1).reshape(
            e, g * cap, d)
        xe = constrain(xe, "model", "batch", None)
    with jax.named_scope("moe.experts"):
        act = activation(cfg.act)
        gg_ = act(jnp.einsum("ecd,edf->ecf", xe, params["gate"],
                             preferred_element_type=jnp.float32))
        uu = jnp.einsum("ecd,edf->ecf", xe, params["up"],
                        preferred_element_type=jnp.float32)
        y = jnp.einsum("ecf,efd->ecd", (gg_ * uu).astype(xe.dtype),
                       params["down"],
                       preferred_element_type=jnp.float32).astype(xt.dtype)
        y = constrain(y, "model", "batch", None)
    with jax.named_scope("moe.combine"):
        yg = y.reshape(e, g, cap, d).swapaxes(0, 1).reshape(g, e * cap, d)
        out = jax.vmap(
            lambda yy, fi, kp, sg: _group_combine(yy, fi, kp, sg, k))(
                yg, flat_idx, keep, slot_gate)          # (G, S_g, d)
        return out.reshape(t, d).astype(jnp.float32)


def moe(engine: ArcaneEngine, params: dict, cfg: ModelConfig,
        x: jax.Array, *, dropless: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (out, aux). With capacity dispatch ``aux`` is the
    load-balancing loss (float32); dropless (serving, which has no loss) it
    is the rows each held expert received, (n_held,) int32."""
    b, s, d = x.shape
    mcfg = cfg.moe
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe.router"):
        shares, ids, gates = route(params, cfg, xt)
    if dropless:
        out, aux = _dropless(engine, params, cfg, xt, ids, gates)
    else:
        with jax.named_scope("moe.router"):
            # load-balancing aux loss (Switch/GShard) over every expert
            ce = jnp.mean(jnp.sum(jax.nn.one_hot(ids, mcfg.n_experts,
                                                 dtype=jnp.float32), axis=1),
                          axis=0)
            aux = (mcfg.n_experts * jnp.sum(jnp.mean(shares, axis=0) * ce)
                   * mcfg.router_aux_coef).astype(jnp.float32)
        out = _capacity(params, cfg, xt, ids, gates)
    out = out.reshape(b, s, d)
    if "shared" in params:
        with jax.named_scope("moe.shared"):
            out = out + mlp(engine, params["shared"], cfg, x).astype(
                jnp.float32)
    return out.astype(x.dtype), aux
