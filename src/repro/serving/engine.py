"""Serving engine: continuous-batching decode over the cache-resident kernels.

A fixed pool of ``max_slots`` sequence slots shares one batched KV cache
(ARCANE's LLC role). Requests are admitted into free slots at any step
(per-slot prefill, inserted into the batch cache with dynamic_update_slice);
every step decodes one token for all live slots. Ragged lengths are free:
the decode kernel skips cache pages past each slot's length, so a just-
admitted short sequence does not pay for its neighbours (the kernel-level
straggler mitigation described in the decode kernel docstring).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import LM

PyTree = Any


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


def _insert_slot(batched: PyTree, one: PyTree, slot: int) -> PyTree:
    """Write a batch-1 cache pytree into slot ``slot`` of the batched cache.

    Cache leaves are (n_periods, B, ...); the singleton cache has B = 1.
    """
    def put(c, n):
        return jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (0, slot) + (0,) * (c.ndim - 2))
    return jax.tree.map(put, batched, one)


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
        self._prefill1 = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)
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
            one_cache = self.model.init_cache(1, self.max_len)
            logits, one_cache = self._prefill1(
                self.params, {"tokens": jnp.asarray(req.prompt[None])},
                one_cache)
            self.cache = _insert_slot(self.cache, one_cache, slot)
            tok = self._sample(logits, req.temperature)
            req.out_tokens.append(int(tok[0]))
            self.slots[slot] = req
            self.positions[slot] = s
            self.last_tokens[slot] = int(tok[0])

    def _sample(self, logits: jax.Array, temperature: float) -> np.ndarray:
        if temperature <= 0.0:
            return np.asarray(jnp.argmax(logits, -1), np.int32)
        self._key, sub = jax.random.split(self._key)
        return np.asarray(
            jax.random.categorical(sub, logits / temperature, -1), np.int32)

    def step(self) -> int:
        """Admit pending requests, decode one token for all live slots.
        Returns number of live slots."""
        self._admit()
        live = [i for i, r in enumerate(self.slots) if r is not None]
        if not live:
            return 0
        tokens = jnp.asarray(self.last_tokens)
        positions = jnp.asarray(self.positions)
        logits, self.cache = self._decode(self.params, tokens, positions,
                                          self.cache)
        lg = np.asarray(logits, np.float32)
        for slot in live:
            req = self.slots[slot]
            tok = self._sample(jnp.asarray(lg[slot : slot + 1]),
                               req.temperature)[0]
            req.out_tokens.append(int(tok))
            self.positions[slot] += 1
            self.last_tokens[slot] = int(tok)
            hit_eos = self.eos_id is not None and int(tok) == self.eos_id
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
