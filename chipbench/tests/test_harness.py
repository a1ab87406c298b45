"""The harness end to end on the CPU, at smoke sizes with interpret-mode
kernels: a closed and an open loop, cells added from files alone (an MoE
configuration with its own equations among them), the censored TTFT tail,
and the command's refusal of anything but a TPU."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from cbench import counts, derive, faults, peaks, reference, spec, tracing, weights
from cbench.counts import gemm_cost
from cbench.harness import program_config, run_cell
from cbench.loop import Req, Step
from cbench.traffic import Draw, Stream
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core.engine import ArcaneEngine
from repro.models.transformer import LM

from conftest import BENCH, EQUATIONS, ROOT, SMOKE_MODELS, int8_control

SEED = 2**31 + 4242
E2E = {"itl_p95_ms", "tokens_per_s", "setup_s"}


def _run(root, cell, seconds=2.0, trace=False, **kw):
    return run_cell(spec.load(root, cell), SEED, seconds, trace,
                    time.perf_counter(), **kw)


def test_closed_loop_cell_from_files(smoke_root):
    out = _run(smoke_root(loop="closed"), "smoke.closed")
    assert out["correct"] is True
    assert set(out["metrics"]) == E2E
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert out["check"]["tokens_checked"]["value"] >= 10
    json.dumps(out)


def test_open_loop_cell(smoke_root):
    root = smoke_root(kind="mla", loop="open")
    out = _run(root, "smoke.open", seconds=3.0)
    assert out["correct"] is True
    # Poisson at 4 requests/s: every request the seed makes due within the
    # 3 s counts (and those due while the last step ran past the close)
    cell = spec.load(root, "smoke.open")
    draws = Stream(cell.mix, SEED, 256)
    due = next(i for i in range(1000) if draws[i].offset_s > 3.0)
    assert 0 < due <= out["attempted"] <= due + 4
    assert out["metrics"]["itl_p95_ms"]["value"] > 0


def test_censored_ttft_and_window_gaps():
    """A request still without its first token enters with its wait so
    far; requests due before the window are not counted."""
    d = Draw(prompt=[1, 2, 3], max_new=4, offset_s=0.0)
    reqs = [Req(d, None, t_due=0.5, t_sent=0.5),                        # stalled
            Req(d, None, t_due=1.0, t_sent=1.0, t_first=1.2, times=[1.2, 1.2, 1.5]),
            Req(d, None, t_due=-1.0, t_sent=-1.0, t_first=0.1, times=[0.1, 0.4])]
    ctx = derive.Context(model={}, mix={}, reqs=reqs, steps=[], window=(0.0, 2.0),
                         setup_s=1.0, compiles_in_window=0, peaks=None)
    assert sorted(derive.ttfts_s(ctx)) == pytest.approx([0.2, 1.5])
    assert sorted(derive.token_gaps_s(ctx)) == pytest.approx([0.0, 0.3, 0.3])
    assert derive.tokens_in_window(ctx) == 5


def test_per_layer_metric_from_new_file(smoke_root):
    """A later cell brings a per-layer metric as a file of its own."""
    root = smoke_root(per_layer=[
        {"name": "serve.batch_occupancy", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "Serving", "moves": "tokens_per_s"},
        {"name": "smoke.steps", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "Serving", "moves": "tokens_per_s"}])
    (root / "chipbench" / "metrics" / "smoke.steps.py").write_text(
        "from cbench import derive\n\n\ndef read(ctx):\n"
        "    return len(derive.window_steps(ctx))\n")
    cell = spec.load(root, "smoke.closed")
    assert [m["name"] for m in cell.per_layer] == ["serve.batch_occupancy", "smoke.steps"]
    out = run_cell(cell, SEED, 2.0, False, time.perf_counter())
    assert set(out["metrics"]) == E2E     # trace 0 reports end-to-end only
    steps = [Step(1.0, 1.1, 3, [], [5, 6, 7]), Step(1.1, 1.2, 3, [], [6, 7, 8]),
             Step(5.0, 5.1, 3, [], [7, 8, 9])]
    ctx = derive.Context(model={}, mix={"max_slots": 3}, reqs=[], steps=steps,
                         window=(1.0, 2.0), setup_s=0.0, compiles_in_window=0,
                         peaks=None)
    assert spec.reader(root, "smoke.steps")(ctx) == 2


# The MoE cell's limit on the mean gap: over the same 600 served tokens a
# seed (15 seeds), sound runs of the float32 smoke program read 0 and its
# equations' int8 control 6.3e-4 to 4.4e-3. Over a run's own sample of ~30
# tokens the control reads 6.5e-5 to 8.4e-3 (15 seeds): too few tokens to
# judge it, so the control test judges 240.
MOE_LIMIT = 2e-4


def _moe_root(smoke_root):
    """A cell whose configuration is the program's MoE FFN, brought as new
    files only: its configuration, traffic, limits and equations."""
    root = smoke_root(kind="moe", limit=MOE_LIMIT)
    (root / "chipbench" / "equations").mkdir()
    shutil.copy(EQUATIONS / "smoke.py", root / "chipbench" / "equations" / "smoke.py")
    return root


def test_default_reference_rejects_moe():
    m = SMOKE_MODELS["moe"]
    params = weights.make_params(
        LM(program_config(m), ArcaneEngine("ref")).param_shapes(), 1)
    with pytest.raises(ValueError, match="dense FFNs only"):
        reference.logits_at(m, params, np.zeros(256, np.int32), np.zeros(128, np.int32))


def test_moe_cell_from_new_files(smoke_root):
    root = _moe_root(smoke_root)
    cell = spec.load(root, "smoke.closed")
    assert cell.equations.logits_at is not reference.logits_at
    out = run_cell(cell, SEED, 2.0, False, time.perf_counter())
    assert out["correct"] is True
    assert out["check"]["token_gap_mean"]["value"] <= MOE_LIMIT
    assert set(out["metrics"]) == E2E


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_moe_cell_fault_fails(smoke_root, monkeypatch, fault):
    monkeypatch.setattr(LM, "decode_step", faults.faulty_decode_step(fault))
    out = _run(_moe_root(smoke_root), "smoke.closed")
    assert out["correct"] is False
    assert out["check"]["token_gap_mean"]["value"] > MOE_LIMIT


def test_moe_int8_control_fails(smoke_root):
    eq = spec.load(_moe_root(smoke_root), "smoke.closed").equations
    ctl_ok, ctl = int8_control(SMOKE_MODELS["moe"], eq, MOE_LIMIT)
    assert ctl_ok is False
    assert ctl["token_gap_mean"][0] > MOE_LIMIT and ctl["tokens_checked"][0] == 240


def test_moe_counts_through_the_cell_equations(smoke_root):
    """The readers take the MoE cell's counts from its equations file: the
    attention projections and the unembedding are GEMM kernel calls, the
    router and the top_k experts' SwiGLU count in the model FLOPs only."""
    cell = spec.load(_moe_root(smoke_root), "smoke.closed")
    m, eq, p = cell.config["model"], cell.equations, peaks.chip_peaks("TPU v5 lite")
    # smoke widths: d 64, 4 heads of 16 (4 kv heads), d_ff 128, vocab 256,
    # 2 layers, 4 experts, top 2; one decode of 3 slots and one prefill of 16
    # GEMMs: q, k, v, o of 2 layers at 3 and at 16 rows, two unembeddings
    gemms = ([gemm_cost(r, 64, 64) for r in (3, 16) for _ in range(2 * 4)]
             + [gemm_cost(3, 64, 256, 4), gemm_cost(1, 64, 256, 4)])
    assert len(eq.gemm_calls(m, 3, 3)) == 2 * 4 + 1
    per_token = 4 * 2 * 64 * 64 + 2 * 64 * 4 + 2 * 3 * 2 * 64 * 128
    lengths = [20, 30, 40]
    decode = sum(2 * (per_token + 4 * n * 4 * 16) + 2 * 64 * 256 for n in lengths)
    prefill = 2 * 16 * per_token + 2 * 4 * 16 * 17 * (16 + 16) + 2 * 64 * 256
    least = counts.least_seconds(gemms, p)
    trace = tracing.Trace(
        window=(0.0, 1.0), modules={0: [tracing.Ev("jit_prefill(7)", 0.1, 0.3)]},
        ops={0: [tracing.Ev("gemm.1", 0.1, 0.1 + least), tracing.Ev("gemm.2", 0.4, 0.4 + least)]},
        host=[tracing.Ev(tracing.WINDOW_SPAN, 0.0, 1.0)])
    ctx = derive.Context(model=m, mix=cell.mix, reqs=[], window=(0.0, 1.0),
                         steps=[Step(0.1, 0.5, 3, [16], lengths, traced=True)],
                         setup_s=0.0, compiles_in_window=0, peaks=p, trace=trace,
                         equations=eq)
    read = {n: spec.reader(cell.root, n)(ctx)
            for n in ("kernel.gemm.roofline", "mfu.decode", "mfu.prefill")}
    assert read["kernel.gemm.roofline"] == pytest.approx(50.0)
    assert read["mfu.decode"] == pytest.approx(100.0 * (decode + prefill) / p.bf16_flops)
    assert read["mfu.prefill"] == pytest.approx(100.0 * prefill / (0.2 * p.bf16_flops))


def test_moe_settled_rows_are_router_near_ties(smoke_root):
    """The MoE equations leave out the rows where rounding at the compute
    precision could swap a chosen expert: all of them where the router's
    logits tie, a few in float32, more in bfloat16, which keeps none that
    float32 leaves out."""
    eq = spec.load(_moe_root(smoke_root), "smoke.closed").equations
    m32 = SMOKE_MODELS["moe"]
    m16 = dict(m32, compute_dtype="bfloat16")
    params = weights.make_params(
        LM(program_config(m32), ArcaneEngine("ref")).param_shapes(), 1)
    tokens = np.random.default_rng(1).integers(0, 256, 256).astype(np.int32)
    rows = np.arange(256)
    s32, s16 = (eq.settled_at(m, params, tokens, rows) for m in (m32, m16))
    assert s32.shape == s16.shape == (256,)
    assert not (s16 & ~s32).any()
    assert 0.5 < s16.mean() < s32.mean() and s32.mean() > 0.95
    tied = dict(params, blocks=tuple(
        dict(b, ffn=dict(b["ffn"], router={"w": b["ffn"]["router"]["w"] * 0}))
        for b in params["blocks"]))
    assert not eq.settled_at(m32, tied, tokens, rows).any()


def test_equations_file_must_be_whole(smoke_root):
    root = smoke_root()
    (root / "chipbench" / "equations").mkdir()
    (root / "chipbench" / "equations" / "smoke.py").write_text(
        "from cbench.reference import logits_at  # noqa: F401\n")
    with pytest.raises(AttributeError, match="gemm_calls"):
        spec.load(root, "smoke.closed")


@pytest.mark.parametrize("make", [get_config, get_smoke_config],
                         ids=["config", "smoke"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_program_config_round_trips(arch, make):
    """Every sub-configuration (moe, mla, mamba, rwkv, the pattern's
    ``LayerSpec``s) comes back from its JSON form as the program's own."""
    c = make(arch)
    m = json.loads(json.dumps(dataclasses.asdict(c)))
    assert program_config(dataclasses.asdict(c)) == c
    assert program_config(m) == c


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "stablelm-3b.decode-backlog", "--seed", str(SEED),
                        "--seconds", "10", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_refuses_a_tree_without_the_program(tmp_path):
    (tmp_path / "chipbench").symlink_to(BENCH)
    (tmp_path / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    p = subprocess.run([sys.executable, str(tmp_path / "chipbench" / "run.py"),
                        "--workload", "stablelm-3b.decode-backlog", "--seed", "1",
                        "--seconds", "10", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeSession:
    """One slot; a step takes 1 s, admits one request and emits one token
    per live request (the admitted one gets its prefill token too)."""

    def __init__(self, clock):
        self.clock, self.slots, self.pending, self.uid = clock, [None], [], 0

    def submit(self, prompt, max_new_tokens):
        class R:
            pass
        r = R()
        r.uid, r.out_tokens, r.done, r.max_new = self.uid, [], False, max_new_tokens
        self.uid += 1
        self.pending.append(r)
        return r

    def step(self):
        self.clock.t += 1.0
        if self.slots[0] is None and self.pending:
            self.slots[0] = self.pending.pop(0)
            self.slots[0].out_tokens.append(0)
        r = self.slots[0]
        if r is None:
            return 0
        r.out_tokens.append(0)
        if len(r.out_tokens) >= r.max_new:
            r.done, self.slots[0] = True, None
        return 1


def test_open_loop_times_from_due_and_waits_when_idle():
    from cbench.loop import Loop
    clock = FakeClock()
    mix = {"loop": "open", "max_slots": 1}
    draws = [Draw([1, 2], 3, 0.5), Draw([1, 2, 3], 3, 0.6), Draw([1], 2, 9.0)]
    lp = Loop(FakeSession(clock), mix, draws, clock=clock, sleep=clock.sleep)
    t0, t1 = lp.run(5.0)
    a, b = lp.reqs
    # idle until 0.5, then a: admitted at once, done after 2 steps (3 tokens)
    assert a.t_due == t0 + 0.5 and a.t_first == t0 + 1.5
    # b was due at 0.6 but waits behind a: its TTFT counts from 0.6
    assert b.t_due == t0 + 0.6 and b.t_first == t0 + 3.5
    assert [len(r.times) for r in (a, b)] == [3, 3]
    assert lp.steps[0].prefill_lens == [2] and lp.steps[0].decode_lens == [3]
    ctx = derive.Context(model={}, mix=mix, reqs=lp.reqs, steps=lp.steps,
                         window=(t0, t1), setup_s=0.0, compiles_in_window=0,
                         peaks=None)
    assert sorted(derive.ttfts_s(ctx)) == pytest.approx([1.0, 2.9])
