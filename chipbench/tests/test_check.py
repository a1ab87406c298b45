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
    from conftest import SMOKE_MODELS
    from cbench import check, weights
    from cbench.harness import program_config
    from cbench.loop import Req
    from cbench.traffic import Draw
    from repro.core.engine import ArcaneEngine
    m = SMOKE_MODELS[kind]
    shapes = LM(program_config(m), ArcaneEngine("ref")).param_shapes()
    params = weights.make_params(shapes, 1)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 256, 16).astype(np.int32)
    handle = SimpleNamespace(uid=0, done=True,
                             out_tokens=rng.integers(0, 256, 240).tolist())
    reqs = [Req(Draw(prompt, 240, 0.0), handle, 0.0, 0.0, slot=0)]
    mix = {"max_len": 256, "output_len": {"uniform": [240, 240]}}
    limits = {"token_gap_mean": LIMIT, "sample": 1, "min_tokens": 100}
    _, (ctl_ok, ctl) = check.run_check(m, params, reqs, SEED, mix, limits,
                                       control=True)
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
