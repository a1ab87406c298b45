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
    ("stablelm-3b", 1.0), ("minicpm3-4b", 0.0), ("moonlight-16b-a3b", 0.0),
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


def test_decode_span_has_no_experts_without_moe(traced_session):
    _, _, spans = traced_session
    assert all("experts_held" not in s[3] for s in spans
               if s[0] == "serve.decode")


def _moonlight_smoke():
    import dataclasses
    cfg = get_smoke_config("moonlight-16b-a3b")
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


def test_moe_session_matches_manual_greedy(rng):
    """Moonlight's smoke model (a dense first layer, MoE layers with a
    held subset, the dropless dispatch): slots decoded together give each
    request's batch-1 greedy tokens."""
    cfg = _moonlight_smoke()
    model = LM(cfg, ArcaneEngine(backend="pallas"))
    params = model.init_params(jax.random.key(0))
    prompts = [np.asarray(rng.integers(0, cfg.vocab, int(n)), np.int32)
               for n in (5, 9, 13)]
    expected = [manual_greedy(model, params, p, 6) for p in prompts]
    sess = ServeSession(model, params, max_slots=2, max_len=128)
    reqs = [sess.submit(p, max_new_tokens=6) for p in prompts]
    sess.run_to_completion()
    assert [r.out_tokens for r in reqs] == expected


def test_decode_span_carries_experts_held(tmp_path):
    """An MoE model's ``serve.decode`` names the routed experts it holds."""
    from jax.profiler import ProfileData
    model = LM(_moonlight_smoke(), ENGINE)
    sess = ServeSession(model, model.init_params(jax.random.key(0)),
                        max_slots=2, max_len=64)
    sess.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    with jax.profiler.trace(str(tmp_path)):
        sess.run_to_completion()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))
    decodes = [dict(e.stats) for plane in ProfileData.from_file(path[-1]).planes
               for line in plane.lines for e in line.events
               if e.name == "serve.decode"]
    assert decodes and all(d["experts_held"] == "2-5/8" for d in decodes)


def test_moe_session_keeps_expert_rows_per_token():
    """Each token of an MoE session carries the rows its program's held
    experts received in each MoE layer: with every expert held, a
    prefill's layers route prompt x top_k rows and a decode's slots x
    top_k, and one array serves all the tokens of a decode step."""
    import dataclasses
    cfg = _moonlight_smoke()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held=(0, cfg.moe.n_experts)))
    model = LM(cfg, ENGINE)
    sess = ServeSession(model, model.init_params(jax.random.key(0)),
                        max_slots=2, max_len=64)
    reqs = [sess.submit(np.arange(n, dtype=np.int32), max_new_tokens=4)
            for n in (5, 9)]
    sess.run_to_completion()
    n_moe = cfg.n_periods * sum(spec.moe for spec in cfg.pattern)
    k = cfg.moe.top_k
    for r in reqs:
        assert len(r.expert_rows) == len(r.out_tokens) == 4
        assert all(a.shape == (n_moe, cfg.moe.n_experts) and a.dtype == np.int32
                   for a in r.expert_rows)
        np.testing.assert_array_equal(r.expert_rows[0].sum(1), len(r.prompt) * k)
        for a in r.expert_rows[1:]:
            np.testing.assert_array_equal(a.sum(1), 2 * k)
    assert reqs[0].expert_rows[1] is reqs[1].expert_rows[1]


def test_dense_model_has_no_expert_rows(rng):
    """Without MoE layers, asking for expert rows traces the same programs,
    the serving steps return (logits, cache) alone, and the session's
    requests keep no rows."""
    from repro.train.step import make_serve_steps
    model = LM(get_smoke_config("stablelm-3b"), ENGINE)
    params = model.param_shapes()
    cache = model.cache_shapes(2, 32)
    tokens = jax.ShapeDtypeStruct((2,), jnp.int32)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 7), jnp.int32)}
    one = model.cache_shapes(1, 32)
    rows = []
    for fn, args in ((model.decode_step, (params, tokens, tokens, cache)),
                     (model.prefill, (params, batch, one))):
        plain = str(jax.make_jaxpr(fn)(*args))
        asked = str(jax.make_jaxpr(lambda *a: fn(*a, expert_rows=rows))(*args))
        assert plain == asked and rows == []
    prefill_step, decode_step = make_serve_steps(model, expert_rows=True)
    assert len(jax.eval_shape(decode_step, params, tokens, tokens, cache)) == 2
    assert len(jax.eval_shape(prefill_step, params, batch, one)) == 2
    sess = ServeSession(model, model.init_params(jax.random.key(0)),
                        max_slots=2, max_len=32)
    req = sess.submit(np.asarray(rng.integers(0, 64, 5), np.int32),
                      max_new_tokens=3)
    sess.run_to_completion()
    assert len(req.out_tokens) == 3 and req.expert_rows == []
