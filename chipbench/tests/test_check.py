"""The output check sees faults of the timed path, and the int8 control.

Faults (``cbench.faults``) are planted under the harness, in the program's
public ``LM.decode_step``, and a whole run is driven through ``run_cell`` at
smoke size; each must come out ``correct: false``. The limit on the mean gap
here (2e-4 logit) is the smoke size's own: sound runs read 0 at this
size, the int8 control 3.1e-4 to 5.5e-4 over 240 positions (seeds 1-3)."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from cbench import faults, spec
from cbench.harness import run_cell
from repro.models.transformer import LM

SEED = 2**31 + 99
LIMIT = 2e-4


def _run(root, cell="smoke.closed", **kw):
    return run_cell(spec.load(root, cell), SEED, 2.0, False,
                    time.perf_counter(), **kw)


def test_sound_run_passes(smoke_root):
    out = _run(smoke_root(limit=LIMIT))
    assert out["correct"] is True
    assert out["check"]["token_gap_mean"]["value"] <= LIMIT


@pytest.mark.parametrize("kind", ["attn", "mla"])
def test_int8_control_fails(kind):
    """The reference in int8 (W8A8), put in the program's place on a
    finished request of 240 served tokens, is judged not correct by the
    check's own comparison: its picks' mean gap below the float32
    reference's best exceeds the limit."""
    from conftest import SMOKE_MODELS, int8_control
    ctl_ok, ctl = int8_control(SMOKE_MODELS[kind], spec.default_equations(), LIMIT)
    assert ctl_ok is False
    assert ctl["token_gap_mean"][0] > LIMIT and ctl["tokens_checked"][0] == 240


@pytest.mark.parametrize("kind", ["attn", "mla"])
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_in_the_timed_path_fails(smoke_root, monkeypatch, kind, fault):
    monkeypatch.setattr(LM, "decode_step", faults.faulty_decode_step(fault))
    out = _run(smoke_root(kind=kind, limit=LIMIT))
    assert out["correct"] is False
    assert out["check"]["token_gap_mean"]["value"] > LIMIT


def test_sample_covers_every_slot():
    """The longest request, one per slot, then draws up to ``k``."""
    from cbench import check
    from cbench.loop import Req
    from cbench.traffic import Draw
    reqs = [Req(Draw(np.zeros(10 + i, np.int32), 4, 0.0),
                SimpleNamespace(uid=i, done=i != 7, out_tokens=[0] * 4),
                0.0, 0.0, slot=i % 4) for i in range(12)]
    got = check.sample(reqs, SEED, 6)
    assert got[0] is reqs[11] and len(got) == 6
    assert {r.slot for r in got} == {0, 1, 2, 3}
    assert all(r.handle.done for r in got)
    assert len({id(r) for r in got}) == 6
    assert len(check.sample(reqs, SEED, 2)) == 4     # every slot, even over k


def test_gaps_leave_out_unsettled_rows():
    """Rows that the equations' ``settled_at`` marks are left out of the
    program's gaps and the control's alike, and so out of the count."""
    import dataclasses

    import jax.numpy as jnp

    from cbench import check

    def logits_at(model, params, tokens, rows, *, quant=False):
        out = np.zeros((len(rows), 8), np.float32)
        out[:, 0] = 1.0                  # the reference's best: token 0
        out[:, 1] = 2.0 if quant else 0.0   # the control's: token 1
        return jnp.asarray(out)

    eq = dataclasses.replace(
        spec.default_equations(), logits_at=logits_at,
        settled_at=lambda model, params, tokens, rows: np.arange(len(rows)) % 2 == 0)
    prog, ctl = check.gaps(eq, {}, None, [5, 6], [0, 3, 0, 3, 3], seq_len=16,
                           n_rows=8, control=True)
    assert prog.tolist() == [0.0, 0.0, 1.0]      # rows 0, 2, 4 of gaps 0 1 0 1 1
    assert ctl.tolist() == [1.0, 1.0, 1.0]
    assert check.judge(prog, {"token_gap_mean": 0.5, "min_tokens": 3})[1][
        "tokens_checked"] == [3, 3]
