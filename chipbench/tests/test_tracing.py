"""Trace reduction: busy union, kernel and program times, idle gaps by
host phase; on synthetic events and on a trace recorded on a TPU v5e."""
import gzip
import json
from pathlib import Path

import pytest

from cbench import derive, tracing
from cbench.loop import Step
from cbench.programs import DECODE, DECODE_ATTENTION, GEMM

FIXTURE = Path(__file__).resolve().parent / "fixtures"
Ev = tracing.Ev


def synthetic():
    ops = [Ev("while.3", 0.1, 0.4),
           Ev("gemm.1", 0.1, 0.3), Ev("fusion.2", 0.25, 0.4),
           Ev("decode_attention.1", 0.6, 0.7), Ev("gemm.9", 1.2, 1.3)]
    mods = [Ev("jit_decode_step(12)", 0.1, 0.4), Ev("jit_prefill(3)", 0.6, 0.7)]
    host = [Ev(tracing.WINDOW_SPAN, 0.0, 1.0), Ev("chipbench.step", 0.0, 0.5),
            Ev("chipbench.account", 0.5, 0.55), Ev("chipbench.wait", 0.55, 1.0)]
    return tracing.Trace(window=(0.0, 1.0), ops={0: ops}, modules={0: mods},
                         host=host)


def test_busy_union_and_clipping():
    tr = synthetic()
    assert tr.busy_intervals(0) == [(0.1, 0.4), (0.6, 0.7)]
    assert tr.busy_s() == pytest.approx(0.4)
    assert tr.window_s == 1.0
    assert tr.op_seconds(GEMM) == pytest.approx(0.2)         # gemm.9 is outside
    assert tr.op_seconds(DECODE_ATTENTION) == pytest.approx(0.1)
    assert tr.module_runs(DECODE) == [pytest.approx(0.3)]


def test_op_name_from_hlo_text():
    assert tracing.op_name("%gemm.48 = bf16[16,6912]{1,0} custom-call(bf16[16,2560] %pad)") \
        == "gemm.48"


def test_top_ops_and_idle_gaps_by_host_phase():
    tr = synthetic()
    top = dict(tr.top_ops())
    assert top == {"gemm": pytest.approx(0.2), "fusion": pytest.approx(0.15),
                   "decode_attention": pytest.approx(0.1)}
    gaps = dict(tr.idle_gaps())
    assert gaps == {"chipbench.wait": pytest.approx(0.3),
                    "chipbench.account": pytest.approx(0.2),
                    "chipbench.step": pytest.approx(0.1)}
    assert sum(gaps.values()) == pytest.approx(tr.window_s - tr.busy_s())


def test_recorded_chip_trace():
    """A few decode steps of the smoke model on a TPU v5e, kernels on."""
    from jax.profiler import ProfileData
    raw = gzip.decompress((FIXTURE / "smoke_decode.xplane.pb.gz").read_bytes())
    tr = tracing.from_profile(ProfileData.from_serialized_xspace(raw))
    rec = json.loads((FIXTURE / "smoke_decode.steps.json").read_text())
    traced = [Step(*s) for s in rec["steps"] if s[5]]
    assert 0 < tr.busy_s() < tr.window_s
    assert tr.op_seconds(GEMM) > 0 and tr.op_seconds(DECODE_ATTENTION) > 0
    assert len(tr.module_runs(DECODE)) == sum(1 for s in traced if s.decode_lens)
    names = {n for n, _ in tr.top_ops(50)}
    assert {"gemm", "decode_attention"} <= names
    idle = sum(v for _, v in tr.idle_gaps(50))
    assert idle == pytest.approx(tr.window_s - tr.busy_s(), rel=1e-6)
    ctx = derive.Context(model=rec["model"], mix={"max_slots": rec["max_slots"]},
                         reqs=[], steps=traced, window=(0.0, 1.0), setup_s=0.0,
                         compiles_in_window=0, peaks=None, trace=tr)
    assert 0 < derive.idle_share(ctx) < 100
