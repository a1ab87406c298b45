"""Train/serve step factories.

``make_train_step`` builds the jittable update: value_and_grad over the model
loss (remat is inside the model's period scan), optional microbatch gradient
accumulation (lax.scan, f32 accumulators — the reduce-scatter of each
microbatch's gradients overlaps the next microbatch's compute under the XLA
scheduler), then the AdamW update. ``make_serve_steps`` builds prefill and
single-token decode steps for the serving shapes.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.transformer import LM
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

PyTree = Any


def make_train_step(model: LM, opt_cfg: AdamWConfig, *,
                    microbatches: int = 1, grad_shardings: PyTree = None):
    """grad_shardings: optional NamedSharding tree (the ZeRO layout). When
    given, gradients are constrained to it right after the backward pass, so
    XLA lowers the data-parallel gradient all-reduce into reduce-scatter (to
    the optimizer shard) + param all-gather — half the gradient wire bytes
    (§Perf iteration 6)."""
    loss_fn = model.loss

    def _constrain_grads(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(
            lambda g, sh: jax.lax.with_sharding_constraint(g, sh),
            grads, grad_shardings)

    def train_step(params: PyTree, opt_state: PyTree, batch: PyTree):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = _constrain_grads(grads)
        else:
            def micro(carry, mb):
                grads_acc, loss_acc = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                grads_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), grads_acc, g)
                return (grads_acc, loss_acc + l), None

            mbs = jax.tree.map(
                lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                    *x.shape[1:]), batch)
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(micro, (zero, jnp.float32(0.0)),
                                            mbs)
            grads = _constrain_grads(
                jax.tree.map(lambda g: g / microbatches, grads))
            loss = loss / microbatches
            metrics = {}
        new_params, new_opt, om = adamw_update(opt_cfg, grads, opt_state,
                                               params)
        out_metrics = {"loss": loss, **{k: v for k, v in metrics.items()
                                        if jnp.ndim(v) == 0}, **om}
        return new_params, new_opt, out_metrics

    return train_step


def make_serve_steps(model: LM, *, enc_len: int = 0,
                     expert_rows: bool = False):
    """Prefill and decode steps, each → (logits, cache). With
    ``expert_rows`` a model with MoE layers' steps also return the rows
    each held expert received in each MoE layer (``LM.prefill``)."""
    def prefill_step(params, batch, cache):
        rows = [] if expert_rows else None
        logits, cache = model.prefill(params, batch, cache, expert_rows=rows)
        return (logits, cache, *(rows or ()))

    def decode_step(params, tokens, position, cache):
        rows = [] if expert_rows else None
        logits, cache = model.decode_step(params, tokens, position, cache,
                                          enc_len=enc_len, expert_rows=rows)
        return (logits, cache, *(rows or ()))

    return prefill_step, decode_step


def init_train_state(model: LM, opt_cfg: AdamWConfig, key) -> tuple:
    params = model.init_params(key)
    return params, adamw_init(opt_cfg, params)
