"""The admission-stall reader (``metrics/serve.admit_ms.py``): on
synthetic requests, on a program that records no admission times, and on
a smoke session driven by the serving loop."""
import types

import pytest

from cbench import derive, harness, loop, spec, traffic
from cbench.loop import Req
from cbench.traffic import Draw

from conftest import ROOT

SEED = 2**31 + 977
READ = spec.reader(ROOT, "serve.admit_ms.decode")
D = Draw(prompt=[1, 2, 3], max_new=4, offset_s=0.0)


def _ctx(handles, window=(10.0, 20.0)):
    reqs = [Req(D, h, t_due=0.0, t_sent=0.0) for h in handles]
    return derive.Context(model={}, mix={}, reqs=reqs, steps=[], window=window,
                          setup_s=0.0, compiles_in_window=0, peaks=None)


def _handle(**kw):
    return types.SimpleNamespace(**kw)


def test_split_names_share_one_reader():
    assert spec.reader_path(ROOT, "serve.admit_ms.decode") == \
        spec.reader_path(ROOT, "serve.admit_ms.prefill")


def test_mean_over_admissions_in_window():
    hs = [_handle(t_admit=11.0, t_first=11.25),
          _handle(t_admit=19.5, t_first=20.5),     # starts in, ends past
          _handle(t_admit=9.0, t_first=10.5),      # started before the window
          _handle(t_admit=20.5, t_first=21.0),     # after it
          _handle(t_admit=None, t_first=None)]     # never admitted
    assert READ(_ctx(hs)) == pytest.approx(1e3 * (0.25 + 1.0) / 2)


@pytest.mark.parametrize("handles", [
    [],
    [types.SimpleNamespace(out_tokens=[1], done=True)],   # no such fields
    [types.SimpleNamespace(t_admit=None, t_first=None)],
    [types.SimpleNamespace(t_admit=12.0, t_first=None)],  # still admitting
    [types.SimpleNamespace(t_admit=30.0, t_first=30.5)],
], ids=["no-requests", "program-without-times", "not-admitted",
        "no-first-token", "outside-window"])
def test_none_where_nothing_to_read(handles):
    assert READ(_ctx(handles)) is None


def test_smoke_session_records_admission_stall(smoke_root):
    """The program's own requests carry both times, and every admission of
    a closed loop after its first step lies in the window."""
    cell = spec.load(smoke_root(loop="closed"), "smoke.closed")
    _, session = harness.build(cell, SEED)
    harness.warm(session, cell.mix, SEED, 256)
    lp = loop.Loop(session, cell.mix, traffic.Stream(cell.mix, SEED, 256))
    lp.preroll()
    lp.run(2.0)
    ctx = derive.Context(model={}, mix=cell.mix, reqs=lp.reqs, steps=lp.steps,
                         window=lp.window, setup_s=0.0, compiles_in_window=0,
                         peaks=None)
    admitted = [r.handle for r in lp.reqs if r.handle.t_admit is not None]
    assert all(h.t_admit <= h.t_first for h in admitted)
    late = [h for h in admitted if h.t_admit >= lp.window[0]]
    assert len(late) >= 3
    assert READ(ctx) == pytest.approx(
        1e3 * sum(h.t_first - h.t_admit for h in late) / len(late))
