"""Serving engine: continuous-batching decode over the cache-resident kernels.

A fixed pool of ``max_slots`` sequence slots shares one batched KV cache
(ARCANE's LLC role). Requests are admitted into free slots at any step
(per-slot prefill, inserted into the batch cache with dynamic_update_slice);
every step decodes one token for all live slots. Ragged lengths are free:
the decode kernel skips cache pages past each slot's length, so a just-
admitted short sequence does not pay for its neighbours (the kernel-level
straggler mitigation described in the decode kernel docstring).

Every phase of ``step()`` runs inside a ``jax.profiler.TraceAnnotation``
span, so a profiler trace shows what the host was doing while the device
waited. The spans cost under a microsecond each with the profiler
off, and change nothing that is jitted::

    serve.step                       one per step()
    ├─ serve.admit  (uid, prompt_len, slot)   one per admission
    │  ├─ serve.init_cache           the batch-1 cache
    │  ├─ serve.prefill              dispatch of the jitted prefill
    │  ├─ serve.insert               the copy into the batched cache
    │  └─ serve.first_token          sampling the first token
    ├─ serve.decode  (inplace_share[, experts_held])
    │                                dispatch of the jitted decode step
    ├─ serve.fetch                   logits to the host
    └─ serve.sample                  per-slot sampling and bookkeeping

The admission spans carry the request's ``uid``. ``serve.decode`` carries
``inplace_share``: the share of the cache's bytes that the decode step
updates in place (``decode_inplace_share``), and for an MoE model
``experts_held``, the routed experts this device computes (``"0-7/64"``).

An MoE model's prefill and decode programs also return the rows each held
expert received in each MoE layer; every request keeps, per output token,
that array of the program that produced it (``Request.expert_rows``), so
a reader of the trace knows which experts' weights each program read.

The decode step donates the batched cache: each step's cache is written in
place, and the previous one is gone once ``step()`` returns.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.blocks import decode_inplace_leaves
from repro.models.transformer import LM
from repro.train.step import make_serve_steps

PyTree = Any


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    # time.perf_counter() when its admission started, and when its first
    # token was on the host: the stall one admission puts on every live slot
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    # MoE models: per output token, the rows each held expert received in
    # each MoE layer of the program that produced it, (n_moe_layers,
    # n_held) int32; a decode step's one array is shared by its tokens
    expert_rows: list = dataclasses.field(default_factory=list)


def _insert_slot(batched: PyTree, one: PyTree, slot: int) -> PyTree:
    """Write a batch-1 cache pytree into slot ``slot`` of the batched cache.

    Cache leaves are (layers, B, ...), the layers of a pattern position's
    stack or the leading dense layers'; the singleton cache has B = 1.
    """
    def put(c, n):
        return jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (0, slot) + (0,) * (c.ndim - 2))
    return jax.tree.map(put, batched, one)


def decode_inplace_share(model: LM, cache_shapes: tuple) -> float:
    """Share of the cache's bytes that ``LM.decode_step`` updates in place,
    from the shapes of a cache (``LM.cache_shapes``); the leaves that take
    the in-place path are those ``blocks.decode_inplace_leaves`` names."""
    total = inplace = 0
    for spec, leaves in zip(model.cfg.cache_specs, cache_shapes):
        names = decode_inplace_leaves(spec)
        for name, leaf in leaves.items():
            nbytes = leaf.size * leaf.dtype.itemsize
            total += nbytes
            inplace += nbytes if name in names else 0
    return inplace / total if total else 0.0


class ServeSession:
    def __init__(self, model: LM, params: PyTree, *, max_slots: int = 4,
                 max_len: int = 512, eos_id: Optional[int] = None,
                 seed: int = 0):
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.cache = model.init_cache(max_slots, max_len)
        self.positions = np.zeros((max_slots,), np.int32)
        self.slots: list[Optional[Request]] = [None] * max_slots
        self.last_tokens = np.zeros((max_slots,), np.int32)
        self._uid = 0
        self._key = jax.random.key(seed)
        self.decode_inplace_share = decode_inplace_share(
            model, model.cache_shapes(max_slots, max_len))
        self._decode_span = {"inplace_share": self.decode_inplace_share}
        if model.cfg.moe is not None:
            lo, hi = model.cfg.moe.held_range
            self._decode_span["experts_held"] = (
                f"{lo}-{hi - 1}/{model.cfg.moe.n_experts}")
        prefill_step, decode_step = make_serve_steps(
            model, expert_rows=any(spec.moe for spec in model.cfg.pattern))

        def prefill(params, batch, cache):       # traced as jit_prefill
            return prefill_step(params, batch, cache)
        self._prefill1 = jax.jit(prefill)
        self._decode = jax.jit(decode_step, donate_argnums=(3,))
        self.pending: list[Request] = []
        self.finished: list[Request] = []

    # ------------------------------------------------------------------ API
    def submit(self, prompt, **kw) -> Request:
        req = Request(uid=self._uid, prompt=np.asarray(prompt, np.int32), **kw)
        s = len(req.prompt)
        if s == 0 or s + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt of {s} tokens + {req.max_new_tokens} new tokens "
                f"does not fit max_len={self.max_len}")
        self._uid += 1
        self.pending.append(req)
        return req

    def _admit(self) -> None:
        for slot in range(self.max_slots):
            if self.slots[slot] is not None or not self.pending:
                continue
            req = self.pending.pop(0)
            s = len(req.prompt)
            req.t_admit = time.perf_counter()
            with TraceAnnotation("serve.admit", uid=req.uid, prompt_len=s,
                                 slot=slot):
                with TraceAnnotation("serve.init_cache", uid=req.uid):
                    one_cache = self.model.init_cache(1, self.max_len)
                with TraceAnnotation("serve.prefill", uid=req.uid):
                    logits, one_cache, *rows = self._prefill1(
                        self.params, {"tokens": jnp.asarray(req.prompt[None])},
                        one_cache)
                with TraceAnnotation("serve.insert", uid=req.uid):
                    self.cache = _insert_slot(self.cache, one_cache, slot)
                with TraceAnnotation("serve.first_token", uid=req.uid):
                    tok = int(self._sample(logits, req.temperature)[0])
                req.t_first = time.perf_counter()
                req.out_tokens.append(tok)
                if rows:
                    req.expert_rows.append(np.asarray(rows[0]))
                self.slots[slot] = req
                self.positions[slot] = s
                self.last_tokens[slot] = tok

    def _sample(self, logits: jax.Array, temperature: float) -> np.ndarray:
        if temperature <= 0.0:
            return np.asarray(jnp.argmax(logits, -1), np.int32)
        self._key, sub = jax.random.split(self._key)
        return np.asarray(
            jax.random.categorical(sub, logits / temperature, -1), np.int32)

    def step(self) -> int:
        """Admit pending requests, decode one token for all live slots.
        Returns number of live slots."""
        with TraceAnnotation("serve.step"):
            self._admit()
            live = [i for i, r in enumerate(self.slots) if r is not None]
            if not live:
                return 0
            with TraceAnnotation("serve.decode", **self._decode_span):
                tokens = jnp.asarray(self.last_tokens)
                positions = jnp.asarray(self.positions)
                logits, self.cache, *rows = self._decode(
                    self.params, tokens, positions, self.cache)
            with TraceAnnotation("serve.fetch"):
                lg = np.asarray(logits, np.float32)
                rows = np.asarray(rows[0]) if rows else None
            with TraceAnnotation("serve.sample"):
                for slot in live:
                    req = self.slots[slot]
                    tok = self._sample(jnp.asarray(lg[slot : slot + 1]),
                                       req.temperature)[0]
                    req.out_tokens.append(int(tok))
                    if rows is not None:
                        req.expert_rows.append(rows)
                    self.positions[slot] += 1
                    self.last_tokens[slot] = int(tok)
                    hit_eos = (self.eos_id is not None
                               and int(tok) == self.eos_id)
                    full = len(req.out_tokens) >= req.max_new_tokens or \
                        self.positions[slot] + 1 >= self.max_len
                    if hit_eos or full:
                        req.done = True
                        self.finished.append(req)
                        self.slots[slot] = None
            return len(live)

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        for _ in range(max_steps):
            if not self.pending and all(s is None for s in self.slots):
                break
            self.step()
        return self.finished
