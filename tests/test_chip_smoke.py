"""chip_smoke.py's serve and compare phases at smoke size on the CPU.

The Pallas engine runs in interpret mode here; on a TPU the same phases run
at stablelm-3b's full widths. ``main()`` must refuse a host without a TPU.
"""
import dataclasses
import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_smoke_config
from repro.core.engine import ArcaneEngine
from repro.models.transformer import LM

_SPEC = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(chip_smoke)

CFG = get_smoke_config("stablelm-3b")
PALLAS = ArcaneEngine(backend="pallas", interpret=True)
REF = ArcaneEngine(backend="ref")


def test_main_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert "'cpu'" in out.err
    assert '"ok"' not in out.out


def test_serve_phase_finishes_every_request():
    model, params = chip_smoke.build(CFG, PALLAS)
    got = chip_smoke.serve(model, params, prompt_lens=(5, 9, 13),
                           max_new=6, max_len=64)
    assert got["requests"] == chip_smoke.N_REQUESTS
    assert got["tokens"] == chip_smoke.N_REQUESTS * 6
    assert got["slots"] == chip_smoke.SLOTS


def test_compare_phase_pallas_matches_ref():
    model, params = chip_smoke.build(CFG, PALLAS)
    ref_model = LM(CFG, REF)
    got = chip_smoke.compare(model, ref_model, params, prompt_len=24,
                             steps=chip_smoke.COMPARE_STEPS, max_len=64)
    assert got["rows"] == 1 + chip_smoke.COMPARE_STEPS
    assert max(got["rel_rms_err"]) <= chip_smoke.REL_RMS_TOL


def test_compare_phase_rejects_a_different_model():
    """The tolerances catch a real fault: full instead of partial rotary."""
    model, params = chip_smoke.build(CFG, PALLAS)
    wrong = LM(dataclasses.replace(CFG, rope_fraction=1.0), REF)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare(model, wrong, params, prompt_len=24,
                           steps=chip_smoke.COMPARE_STEPS, max_len=64)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    import jax
    from repro.launch import chip
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert chip.use_compile_cache() == str(chip.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(chip.CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_launchers_refuse_pallas_without_a_tpu():
    from repro.launch import serve, train
    with pytest.raises(SystemExit, match="needs a TPU"):
        serve.run(["--arch", "stablelm-3b", "--smoke"])
    with pytest.raises(SystemExit, match="no backward pass"):
        train.run(["--arch", "stablelm-3b", "--smoke", "--steps", "1"])
