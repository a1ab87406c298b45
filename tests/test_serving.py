"""Serving engine: greedy parity with manual decode + continuous batching."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.engine import ArcaneEngine
from repro.models.transformer import LM
from repro.serving.engine import ServeSession, decode_inplace_share

ENGINE = ArcaneEngine(backend="ref")


def manual_greedy(model, params, prompt, n_new, max_len=128):
    cache = model.init_cache(1, max_len)
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(prompt[None])}, cache)
    toks = [int(jnp.argmax(logits, -1)[0])]
    step = jax.jit(model.decode_step)
    pos = len(prompt)
    for _ in range(n_new - 1):
        lg, cache = step(params, jnp.asarray([toks[-1]], jnp.int32),
                         jnp.asarray([pos], jnp.int32), cache)
        toks.append(int(jnp.argmax(lg, -1)[0]))
        pos += 1
    return toks


def test_session_matches_manual_greedy(rng):
    cfg = get_smoke_config("stablelm-3b")
    model = LM(cfg, ENGINE)
    params = model.init_params(jax.random.key(0))
    prompts = [np.asarray(rng.integers(0, cfg.vocab, int(n)), np.int32)
               for n in (5, 9, 13)]
    expected = [manual_greedy(model, params, p, 6) for p in prompts]

    sess = ServeSession(model, params, max_slots=2, max_len=128)
    reqs = [sess.submit(p, max_new_tokens=6) for p in prompts]
    sess.run_to_completion()
    for req, exp in zip(reqs, expected):
        assert req.out_tokens == exp, (req.out_tokens, exp)


def test_continuous_batching_admits_when_slot_frees(rng):
    cfg = get_smoke_config("stablelm-3b")
    model = LM(cfg, ENGINE)
    params = model.init_params(jax.random.key(0))
    sess = ServeSession(model, params, max_slots=2, max_len=64)
    for i in range(5):
        sess.submit(rng.integers(0, cfg.vocab, 4), max_new_tokens=3)
    done = sess.run_to_completion()
    assert len(done) == 5
    assert all(len(r.out_tokens) == 3 for r in done)


def test_ragged_lengths_isolated(rng):
    """Slot contents must not leak across sequences: same prompt in slot 0
    decodes identically regardless of the neighbour in slot 1."""
    cfg = get_smoke_config("gemma2-9b")
    model = LM(cfg, ENGINE)
    params = model.init_params(jax.random.key(0))
    p = np.asarray(rng.integers(0, cfg.vocab, 7), np.int32)
    other1 = np.asarray(rng.integers(0, cfg.vocab, 3), np.int32)
    other2 = np.asarray(rng.integers(0, cfg.vocab, 15), np.int32)

    def run_with(other):
        sess = ServeSession(model, params, max_slots=2, max_len=64)
        r = sess.submit(p, max_new_tokens=5)
        sess.submit(other, max_new_tokens=5)
        sess.run_to_completion()
        return r.out_tokens

    assert run_with(other1) == run_with(other2)


# ------------------------------------------------------- in-place decode
def test_step_donates_the_previous_cache(rng):
    cfg = get_smoke_config("stablelm-3b")
    model = LM(cfg, ENGINE)
    sess = ServeSession(model, model.init_params(jax.random.key(0)),
                        max_slots=2, max_len=64)
    sess.submit(rng.integers(0, cfg.vocab, 5), max_new_tokens=4)
    sess.step()
    before = jax.tree.leaves(sess.cache)
    sess.step()
    assert all(leaf.is_deleted() for leaf in before)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(sess.cache))


@pytest.mark.parametrize("arch,share", [
    ("stablelm-3b", 1.0), ("minicpm3-4b", 0.0),
    ("jamba-1.5-large-398b", None), ("whisper-large-v3", None)])
def test_decode_inplace_share(arch, share):
    """1 where every leaf is self-attention K/V, 0 where none is, strictly
    between for mamba beside attention and for the cross-attention K/V."""
    model = LM(get_smoke_config(arch), ENGINE)
    if model.cfg.enc_dec:       # a session has no encoder: shapes alone
        got = decode_inplace_share(model,
                                   model.cache_shapes(2, 64, enc_len=16))
    else:
        got = ServeSession(model, None, max_slots=2,
                           max_len=64).decode_inplace_share
    if share is None:
        assert 0.0 < got < 1.0
    else:
        assert got == share


def test_decode_step_writes_no_whole_layer():
    """The served decode step, lowered on the kernel path, neither slices
    nor updates a whole layer's K or V: only single positions go in, and
    the kernel reads the layer where it lies in the stack."""
    cfg = get_smoke_config("stablelm-3b")
    model = LM(cfg, ArcaneEngine(backend="pallas"))
    sess = ServeSession(model, model.param_shapes(), max_slots=2, max_len=64)
    k = sess.cache[0]["k"]
    assert k.shape[0] == 2                     # two layers in the stack
    layer = "x".join(map(str, k.shape[1:]))
    dtype = {"bfloat16": "bf16", "float32": "f32"}[str(k.dtype)]
    whole = {f"tensor<{layer}x{dtype}>", f"tensor<1x{layer}x{dtype}>"}
    ints = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = sess._decode.lower(model.param_shapes(), ints, ints,
                              sess.cache).as_text()
    ops = [ln for ln in text.splitlines()
           if re.search(r"stablehlo\.dynamic_(update_)?slice", ln)]
    assert ops, "no dynamic slices at all: the parse is wrong"
    for ln in ops:
        assert not whole & set(re.findall(r"tensor<[^>]*>", ln)), ln


# ---------------------------------------------------------------- spans
PHASES = ("serve.decode", "serve.fetch", "serve.sample")
ADMIT_PHASES = ("serve.init_cache", "serve.prefill", "serve.insert",
                "serve.first_token")


@pytest.fixture(scope="module")
def traced_session(tmp_path_factory):
    """The same greedy requests served with the profiler off, then on; the
    second run's ``serve.*`` host spans as (name, start, end, args)."""
    from jax.profiler import ProfileData

    cfg = get_smoke_config("stablelm-3b")
    model = LM(cfg, ENGINE)
    params = model.init_params(jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, int(n)) for n in (5, 9, 3, 12, 6)]

    def serve():
        sess = ServeSession(model, params, max_slots=2, max_len=64)
        reqs = [sess.submit(p, max_new_tokens=4) for p in prompts]
        lives = []
        while sess.pending or any(s is not None for s in sess.slots):
            lives.append(sess.step())
        return reqs, lives

    off = serve()
    out = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(out)):
        on = serve()
    path = sorted(glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path[-1]).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    return off, on, spans


def _inside(spans, outer, name):
    return [s for s in spans if s[0] == name
            and outer[1] <= s[1] and s[2] <= outer[2]]


def test_step_spans_hold_one_of_each_phase(traced_session):
    _, (_, lives), spans = traced_session
    steps = sorted((s for s in spans if s[0] == "serve.step"),
                   key=lambda s: s[1])
    assert len(steps) == len(lives) and all(n > 0 for n in lives)
    for st in steps:
        for name in PHASES:
            assert len(_inside(spans, st, name)) == 1, (name, st)
    for name in PHASES:
        assert sum(1 for s in spans if s[0] == name) == len(steps)


def test_admission_spans_carry_uid_and_four_children(traced_session):
    _, (reqs, _), spans = traced_session
    for r in reqs:
        admits = [s for s in spans
                  if s[0] == "serve.admit" and s[3].get("uid") == r.uid]
        assert len(admits) == 1, r.uid
        adm = admits[0]
        assert adm[3]["prompt_len"] == len(r.prompt)
        for name in ADMIT_PHASES:
            kids = _inside(spans, adm, name)
            assert len(kids) == 1 and kids[0][3]["uid"] == r.uid, name
    assert sum(1 for s in spans if s[0] == "serve.admit") == len(reqs)


def test_admission_times_ordered(traced_session):
    (reqs_off, _), (reqs_on, _), _ = traced_session
    for r in reqs_off + reqs_on:
        assert r.t_admit is not None and r.t_admit <= r.t_first


def test_profiler_leaves_tokens_unchanged(traced_session):
    (reqs_off, lives_off), (reqs_on, lives_on), _ = traced_session
    assert lives_off == lives_on
    assert [r.out_tokens for r in reqs_off] == [r.out_tokens for r in reqs_on]
    assert all(len(r.out_tokens) == 4 for r in reqs_on)


def test_decode_span_carries_inplace_share(traced_session):
    _, _, spans = traced_session
    decodes = [s for s in spans if s[0] == "serve.decode"]
    assert decodes and all(s[3]["inplace_share"] == 1.0 for s in decodes)
