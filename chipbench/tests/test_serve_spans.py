"""Idle gaps under the program's own spans (``serve.*``, written by
``ServeSession``) where they join the harness's ``chipbench.*`` spans in a
trace's host spans: on synthetic events, and on a trace of the smoke
session recorded on a TPU v5e with the kernels on
(``record_serve_trace.py``)."""
import gzip
import json
from pathlib import Path

import pytest

from cbench import tracing
from cbench.loop import Step
from cbench.programs import DECODE, DECODE_ATTENTION, GEMM, PREFILL

FIXTURE = Path(__file__).resolve().parent / "fixtures"
PROGRAM = "serve."
Ev = tracing.Ev


def with_program_spans(pd, tr):
    """``tr`` from ``tracing.from_profile(pd)`` with the program's host
    spans added beside the harness's."""
    tr.host = tr.host + [
        Ev(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
        for plane in pd.planes if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith(PROGRAM)]
    return tr


def test_program_spans_take_the_gaps_inside_a_step():
    ops = [Ev("gemm.1", 0.04, 0.30), Ev("decode_attention.1", 0.36, 0.46),
           Ev("gemm.2", 0.50, 0.52), Ev("gemm.3", 0.80, 0.85)]
    harness = [Ev(tracing.WINDOW_SPAN, 0.0, 1.0),
               Ev("chipbench.step", 0.0, 0.80),
               Ev("chipbench.account", 0.80, 1.0)]
    program = [Ev("serve.step", 0.03, 0.79), Ev("serve.admit", 0.03, 0.40),
               Ev("serve.prefill", 0.03, 0.30), Ev("serve.insert", 0.30, 0.36),
               Ev("serve.decode", 0.40, 0.46), Ev("serve.fetch", 0.46, 0.50),
               Ev("serve.sample", 0.50, 0.78)]

    def gaps(host):
        return dict(tracing.Trace(window=(0.0, 1.0), ops={0: ops},
                                  modules={0: []}, host=host).idle_gaps())

    assert gaps(harness) == {"chipbench.step": pytest.approx(0.42),
                             "chipbench.account": pytest.approx(0.15)}
    assert gaps(harness + program) == {
        "chipbench.step": pytest.approx(0.04),      # before serve.step opens
        "serve.insert": pytest.approx(0.06),
        "serve.fetch": pytest.approx(0.04),
        "serve.sample": pytest.approx(0.28),
        "chipbench.account": pytest.approx(0.15)}


def _load(stem):
    from jax.profiler import ProfileData
    raw = gzip.decompress((FIXTURE / f"{stem}.xplane.pb.gz").read_bytes())
    pd = ProfileData.from_serialized_xspace(raw)
    rec = json.loads((FIXTURE / f"{stem}.steps.json").read_text())
    return pd, rec


def test_program_spans_move_no_existing_reading():
    """Adding the program's spans changes only what ``idle_gaps`` names:
    busy time, operations, programs and the idle total stay."""
    pd, _ = _load("smoke_serve")
    base = tracing.from_profile(pd)
    tr = with_program_spans(pd, tracing.from_profile(pd))
    assert len(tr.host) > len(base.host)
    assert tr.window == base.window and tr.busy_s() == base.busy_s()
    assert tr.top_ops(50) == base.top_ops(50)
    for pat in (GEMM, DECODE_ATTENTION):
        assert tr.op_seconds(pat) == base.op_seconds(pat)
    for pat in (DECODE, PREFILL):
        assert tr.module_runs(pat) == base.module_runs(pat)
    assert sum(v for _, v in tr.idle_gaps(100)) == \
        pytest.approx(sum(v for _, v in base.idle_gaps(100)), rel=1e-9)
    # smoke_decode was recorded before the program had spans: nothing to add
    old_pd, _ = _load("smoke_decode")
    old = tracing.from_profile(old_pd)
    assert with_program_spans(old_pd, tracing.from_profile(old_pd)).host \
        == old.host


def test_recorded_idle_inside_step_falls_under_program_spans():
    """On the chip's clock, at least 90% of the device's idle time inside
    ``chipbench.step`` lies under a ``serve.*`` span."""
    pd, rec = _load("smoke_serve")
    tr = with_program_spans(pd, tracing.from_profile(pd))
    traced = [Step(*s) for s in rec["steps"] if s[5]]
    assert len(tr.module_runs(DECODE)) == \
        sum(1 for s in traced if s.decode_lens)
    names = {h.name for h in tr.host}
    assert {"serve.step", "serve.admit", "serve.prefill", "serve.decode",
            "serve.fetch", "serve.sample"} <= names
    gaps = dict(tr.idle_gaps(100))
    in_step = {k: v for k, v in gaps.items()
               if k == "chipbench.step" or k.startswith(PROGRAM)}
    program = sum(v for k, v in in_step.items() if k != "chipbench.step")
    assert program > 0 and program >= 0.9 * sum(in_step.values())
