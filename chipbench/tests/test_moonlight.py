"""Moonlight-16B-A3B's cell from its files: its equations against the
program's smoke model served through ``ServeSession`` on the CPU (Pallas
kernels in interpret mode), its settled rows, its counts and the
``kernel.expert_gemm.roofline`` reader."""
import dataclasses
import json
import shutil
import time
import types

import numpy as np
import pytest

from cbench import counts, derive, peaks, spec, tracing, weights
from cbench.counts import gemm_cost
from cbench.harness import program_config, run_cell
from cbench.loop import Req, Step
from repro.configs import get_config
from repro.configs.moonlight_16b_a3b import smoke
from repro.core.engine import ArcaneEngine
from repro.models.transformer import LM

from conftest import BENCH, ROOT, int8_control, make_root

CONFIG = "moonlight-16b-a3b"
SEED = 2**31 + 1616
# Float32 smoke program against its float32 equations: the served tokens
# are the reference's own greedy picks to float32 rounding.
LIMIT = 1e-4


def _smoke_model() -> dict:
    return json.loads(json.dumps(dataclasses.asdict(dataclasses.replace(
        smoke(), param_dtype="float32", compute_dtype="float32"))))


@pytest.fixture
def moon_root(tmp_path):
    """A smoke cell whose configuration is Moonlight's smoke model and
    whose equations are the benchmark's own Moonlight file."""
    root = make_root(tmp_path, limit=LIMIT)
    base = root / "chipbench"
    (base / "configs" / "smoke.json").write_text(json.dumps(
        {"engine": "pallas", "model": _smoke_model()}))
    (base / "equations").mkdir()
    shutil.copy(BENCH / "equations" / f"{CONFIG}.py", base / "equations" / "smoke.py")
    return root


def _params(m, seed=1):
    return weights.make_params(LM(program_config(m), ArcaneEngine("ref")).param_shapes(),
                               seed)


def test_config_model_block_round_trips():
    """The configuration's ``model`` block is the registry's Moonlight with
    experts 0-7 held, and comes back from JSON as the program's own."""
    m = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())["model"]
    want = get_config(CONFIG)
    want = dataclasses.replace(want, moe=dataclasses.replace(want.moe, held=(0, 8)))
    assert program_config(m) == want
    assert program_config(json.loads(json.dumps(dataclasses.asdict(want)))) == want


def test_smoke_served_agrees_with_equations(moon_root):
    """Prefill and decode through the cache with the dropless expert_gemm
    dispatch, judged at the settled rows against the equations' float32
    reference, over enough tokens."""
    out = run_cell(spec.load(moon_root, "smoke.closed"), SEED, 2.0, False,
                   time.perf_counter())
    assert out["correct"] is True
    assert out["check"]["token_gap_mean"]["value"] <= LIMIT
    assert out["check"]["tokens_checked"]["value"] >= 10


def test_int8_control_fails(moon_root):
    eq = spec.load(moon_root, "smoke.closed").equations
    ok, ctl = int8_control(_smoke_model(), eq, LIMIT)
    assert ok is False and ctl["token_gap_mean"][0] > LIMIT


def test_settled_rows(moon_root):
    """Most rows settle in float32, fewer at the bfloat16 unit, none where
    the router's scores and its bias tie every expert."""
    eq = spec.load(moon_root, "smoke.closed").equations
    m32 = _smoke_model()
    m16 = dict(m32, compute_dtype="bfloat16")
    params = _params(m32)
    tokens = np.random.default_rng(1).integers(0, 256, 256).astype(np.int32)
    rows = np.arange(256)
    s32, s16 = (eq.settled_at(m, params, tokens, rows) for m in (m32, m16))
    assert s32.shape == (256,) and not (s16 & ~s32).any()
    assert s16.mean() < s32.mean() and s32.mean() > 0.95
    flat = dict(params, blocks=tuple(
        dict(b, ffn=dict(b["ffn"], router={"w": b["ffn"]["router"]["w"] * 0,
                                           "bias": b["ffn"]["router"]["bias"] * 0}))
        for b in params["blocks"]))
    assert not eq.settled_at(m32, flat, tokens, rows).any()


def test_counts_and_expert_gemm_reader():
    """GEMM kernel calls: 27 layers of q, kv_down, o, the dense layer's FFN
    and 26 layers of shared experts, and the unembedding. The expert_gemm
    reader prices each traced decode from the rows its held experts
    received (``Request.expert_rows``): 6 rows to each of 8 held experts
    through gate, up and down of 26 layers reads every held expert's
    weights once, a decode that routed rows to 2 of them reads 2; the
    kernel's time is its events inside the decode executions, and a
    prefill's rows and events are left out."""
    cell = spec.load(ROOT, f"{CONFIG}.decode-backlog-64")
    m, eq, p = cell.config["model"], cell.equations, peaks.chip_peaks("TPU v5 lite")
    calls = eq.gemm_calls(m, 64, 64)
    assert len(calls) == 27 * 6 + 1
    assert calls[3] == gemm_cost(64, 2048, 11264) and calls[9] == gemm_cost(64, 2048, 2816)
    assert calls[-1] == gemm_cost(64, 2048, 163840, 4)
    rows, weight = 48, 8 * 2048 * 1408
    expert = [(2 * rows * 2048 * 1408, 2 * (weight + rows * (2048 + 1408)))] * (3 * 26)
    least = counts.least_seconds(expert, p)
    assert least == pytest.approx(26 * 3 * 2 * weight / p.hbm_bw, rel=0.01)
    trace = tracing.Trace(
        window=(0.0, 1.0),
        modules={0: [tracing.Ev("jit_prefill(3)", 0.01, 0.04),
                     tracing.Ev("jit_decode_step(7)", 0.05, 0.9)]},
        ops={0: [tracing.Ev("expert_gemm.7", 0.02, 0.03),
                 tracing.Ev("expert_gemm.3", 0.1, 0.1 + least),
                 tracing.Ev("expert_gemm", 0.5, 0.5 + least),
                 tracing.Ev("gemm.4", 0.7, 0.9)]},
        host=[tracing.Ev(tracing.WINDOW_SPAN, 0.0, 1.0)])
    spread = np.full((26, 8), 6, np.int32)             # 48 rows, all 8 touched
    two = np.zeros((26, 8), np.int32)
    two[:, 3], two[:, 5] = 40, 8                       # 48 rows, 2 touched
    prefill = np.full((26, 8), 96, np.int32)

    def ctx_of(decode, handles=None):
        """63 requests mid-sequence and one admitted in the traced step,
        whose first token is its prefill's."""
        handles = handles or (
            [types.SimpleNamespace(expert_rows=[spread, decode])] * 63
            + [types.SimpleNamespace(expert_rows=[prefill, decode])])
        reqs = [Req(draw=None, handle=h, t_due=0.0, t_sent=0.0,
                    times=[0.95 if i == 63 else -1.0, 0.95])
                for i, h in enumerate(handles)]
        return derive.Context(
            model=m, mix=cell.mix, reqs=reqs, window=(0.0, 1.0),
            steps=[Step(0.0, 0.95, 64, [128], [300] * 64, traced=True)],
            setup_s=0.0, compiles_in_window=0, peaks=p, trace=trace,
            equations=eq)
    reader = spec.reader(ROOT, "kernel.expert_gemm.roofline")
    assert reader(ctx_of(spread)) == pytest.approx(50.0)
    least_two = counts.least_seconds(
        [(2 * rows * 2048 * 1408, 2 * (weight // 4 + rows * (2048 + 1408)))]
        * (3 * 26), p)
    assert reader(ctx_of(two)) == pytest.approx(50.0 * least_two / least)
    assert reader(ctx_of(two)) < 0.3 * reader(ctx_of(spread))
    # a program that keeps no rows (a dense model, or a session without
    # them) leaves the metric out, as does a trace with no decode
    assert reader(ctx_of(None, [types.SimpleNamespace()] * 64)) is None
    no_decode = dataclasses.replace(trace, modules={0: trace.modules[0][:1]})
    assert reader(dataclasses.replace(ctx_of(spread), trace=no_decode)) is None
    dense = dict(m)
    del dense["moe"]
    assert reader(dataclasses.replace(ctx_of(spread), model=dense)) is None
    # model FLOPs: 0.75 held experts a token, not 6
    per_tok = eq.model_flops_decode(m, 0)
    full = dict(m, moe=dict(m["moe"], held=[0, 64]))
    assert eq.model_flops_decode(full, 0) - per_tok == pytest.approx(
        26 * (6 - 0.75) * 3 * 2 * 2048 * 1408)
