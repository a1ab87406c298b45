"""The output check that decides ``correct``.

Once the window has closed and the session is freed, a sample of the
finished requests is run through the configuration's float32 reference
(its ``logits_at``, ``cbench.spec.Equations``) over its prompt and its
served tokens. The sample holds the longest finished request, one drawn
from the seed among those each slot served (so a fault in one slot of the
batch is seen), and further draws up to the cell's ``sample``. For every
served token the reference gives the gap by which that token's logit lies
below the reference's best at that position (0 where the served token is the
reference's own greedy choice). Positions at which the reference's answer
hangs on a discrete choice within the configuration's rounding (its
``settled_at``; a router's top-k) are left out, and the count of tokens
checked is the count of the rest. The mean gap is compared with the cell's
limit. Served tokens are greedy choices of the program's bfloat16 logits,
so a sound run's gaps stay at the size of bfloat16 rounding among
near-ties; a wrong layer, cache row or position moves them by the logits'
own scale.

The first served token comes from the prefill and the rest from decode
steps through the cache, so one sample covers both paths.

The control is the same reference in int8 (W8A8) put in the program's
place: at the same positions its greedy picks are judged by the same
comparison, and have to come out not correct.
"""
from __future__ import annotations

import numpy as np

from cbench.traffic import rng_for


def sample(reqs: list, seed: int, k: int) -> list:
    """The longest finished request, one per slot that finished any, then
    draws from the seed up to ``k`` in all."""
    done = sorted((r for r in reqs if r.handle.done),
                  key=lambda r: (len(r.draw.prompt) + len(r.handle.out_tokens),
                                 r.handle.uid))
    if not done:
        return []
    rng = rng_for(seed, 5)
    picked = [done[-1]]
    by_slot: dict = {}
    for r in done[:-1]:
        by_slot.setdefault(r.slot, []).append(r)
    for slot in sorted(s for s in by_slot if s is not None and s != done[-1].slot):
        group = by_slot[slot]
        picked.append(group[int(rng.integers(len(group)))])
    rest = [r for r in done if not any(r is p for p in picked)]
    more = rng.permutation(len(rest))[: max(0, k - len(picked))]
    picked += [rest[i] for i in sorted(more)]
    return picked


def _rows(prompt, toks, seq_len: int, n_rows: int):
    """Padded sequence, padded logit rows and padded targets of one request."""
    s, n = len(prompt), len(toks)
    if s + n - 1 > seq_len or n > n_rows:
        raise ValueError(f"request of {s}+{n} tokens exceeds the check's "
                         f"{seq_len} positions or {n_rows} rows")
    seq = np.zeros(seq_len, np.int32)
    seq[: s + n - 1] = np.concatenate([prompt, toks[:-1]])
    rows = np.full(n_rows, s - 1, np.int32)
    rows[:n] = np.arange(s - 1, s + n - 1)
    tgt = np.zeros(n_rows, np.int32)
    tgt[:n] = toks
    return seq, rows, tgt


def gaps(eq, model: dict, params, prompt, toks, *, seq_len: int,
         n_rows: int, control: bool = False) -> list[np.ndarray]:
    """Per served token at a settled row (``eq.settled_at``), the
    reference's best logit minus its logit of the served token; with
    ``control`` also, at the same rows, the gap of the int8 control's
    greedy choice (the sequence still holds the program's tokens). ``eq``
    is the configuration's ``cbench.spec.Equations``."""
    import jax.numpy as jnp
    seq, rows, tgt = _rows(np.asarray(prompt), np.asarray(toks), seq_len, n_rows)
    ref = eq.logits_at(model, params, seq, rows)
    picks = [jnp.asarray(tgt)]
    if control:
        picks.append(jnp.argmax(eq.logits_at(model, params, seq, rows, quant=True),
                                -1))
    keep = np.asarray(eq.settled_at(model, params, seq, rows), bool)[: len(toks)]
    best = ref.max(-1)
    return [np.asarray(best - jnp.take_along_axis(ref, p[:, None], -1)[:, 0],
                       np.float64)[: len(toks)][keep] for p in picks]


def judge(g: np.ndarray, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: [value, limit]}) for the gaps ``g`` of the
    sampled tokens. Compared: the mean gap against
    ``limits["token_gap_mean"]``, and the count of tokens checked against
    the least ``limits["min_tokens"]``. The mean, not the widest gap: a
    sound run's gaps come from a few near-ties flipped by rounding, whose
    count and size both grow with the error, so the mean grows with its
    square and parts a lower precision from bfloat16 by far more than the
    widest gap does. The widest gap is printed beside it, with no limit."""
    mean = float(g.mean()) if g.size else float("inf")
    numbers = {"token_gap_mean": [mean, limits["token_gap_mean"]],
               "tokens_checked": [int(g.size), limits["min_tokens"]],
               "token_gap_max": [float(g.max()) if g.size else float("inf"), None]}
    ok = bool(np.isfinite(mean) and mean <= limits["token_gap_mean"]
              and g.size >= limits["min_tokens"])
    return ok, numbers


def run_check(eq, model: dict, params, reqs: list, seed: int, mix: dict,
              limits: dict, *, control: bool = False) -> list[tuple[bool, dict]]:
    """``[judge(program's gaps)]``, and with ``control`` a second entry,
    ``judge(control's gaps)`` on the same sample, both against the
    reference of the equations ``eq``."""
    picked = sample(reqs, seed, limits["sample"])
    n_rows = -(-max(_max_out(mix), 1) // 128) * 128
    kw = dict(seq_len=mix["max_len"], n_rows=n_rows, control=control)
    per_req = [gaps(eq, model, params, r.draw.prompt,
                    list(r.handle.out_tokens), **kw)
               for r in picked]
    cols = zip(*per_req) if per_req else [[]] * (1 + control)
    return [judge(np.concatenate(c) if c else np.zeros(0), limits) for c in cols]


def _max_out(mix: dict) -> int:
    spec = mix["output_len"]
    return max(spec["values"]) if "values" in spec else spec["uniform"][1]
