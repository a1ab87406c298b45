"""The one traffic generator: a mix file of parameters in, requests out.

Everything is drawn from the run's ``--seed``: each request's prompt length
and output length independently from the mix's distributions, its token
ids, and, in an open loop, its arrival, with exponential gaps at the mix's
rate (a Poisson process). The same seed gives the same requests; another
seed gives another arrival pattern and another draw of sizes from the same
distributions.

A mix file (``chipbench/traffic/<mix>.json``) holds:

* ``loop``: ``"closed"`` (``clients`` callers, each sends its next request
  when its previous one finishes) or ``"open"`` (Poisson arrivals at
  ``rate`` requests per second, sent on schedule whatever the system does);
* ``max_slots`` and ``max_len``: the serving session the mix is sent to;
* ``prompt_len``: ``{"values": [...], "weights": [...]}``, a fixed set of
  lengths (one prefill compile each) with their shares;
* ``output_len``: the same form, or ``{"uniform": [lo, hi]}`` (integers
  in ``[lo, hi]``);
* ``source``: the public trace or benchmark the lengths follow.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Draw:
    prompt: np.ndarray     # (S,) int32 token ids
    max_new: int
    offset_s: float        # open loop: due time after the window opens


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per stream; ``seed`` may exceed 32 bits."""
    return np.random.default_rng([stream, seed & 0xFFFFFFFF, seed >> 32])


def _weights(spec: dict) -> np.ndarray:
    w = np.asarray(spec["weights"], np.float64)
    if len(w) != len(spec["values"]) or (w < 0).any() or abs(w.sum() - 1) > 1e-9:
        raise ValueError(f"weights {spec['weights']} of {spec['values']} "
                         f"are not shares summing to 1")
    return w


def _draw(rng: np.random.Generator, spec: dict) -> int:
    if "values" in spec:
        return int(rng.choice(spec["values"], p=_weights(spec)))
    lo, hi = spec["uniform"]
    return int(rng.integers(lo, hi + 1))


def sizes(spec: dict) -> list[int]:
    """Every prompt length the spec can draw (the shapes set-up warms)."""
    _weights(spec)
    return sorted(set(spec["values"]))


class Stream:
    """The mix's request stream, drawn as far as it is read: ``stream[i]``
    is the i-th request, the same for every reader of one seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self._sizes = rng_for(seed, 1)
        self._gaps = rng_for(seed, 2)
        self._tok = rng_for(seed, 3)
        self._t = 0.0
        self._out: list[Draw] = []

    def __getitem__(self, i: int) -> Draw:
        while len(self._out) <= i:
            if self.mix["loop"] == "open":
                self._t += float(self._gaps.exponential(1.0 / float(self.mix["rate"])))
            p = _draw(self._sizes, self.mix["prompt_len"])
            o = _draw(self._sizes, self.mix["output_len"])
            self._out.append(Draw(
                prompt=self._tok.integers(0, self.vocab, p, dtype=np.int32),
                max_new=o, offset_s=self._t))
        return self._out[i]
