"""CPU tests of the chip benchmark's yardstick, at smoke sizes.

Run from the repository root: ``python -m pytest chipbench/tests``.
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import pytest  # noqa: E402

SMOKE_MODELS = {
    "attn": {"name": "stablelm-smoke", "family": "dense", "n_layers": 2,
             "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_ff": 128,
             "vocab": 256, "head_dim": 16, "pattern": [{"kind": "attn"}],
             "rope_fraction": 0.25, "rope_theta": 10000.0,
             "norm": "layernorm", "act": "silu", "qkv_bias": False,
             "tie_embeddings": False, "max_seq_len": 512},
    "mla": {"name": "minicpm3-smoke", "family": "dense", "n_layers": 2,
            "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_ff": 128,
            "vocab": 256, "head_dim": 24, "pattern": [{"kind": "mla"}],
            "mla": {"q_lora_rank": 32, "kv_lora_rank": 16,
                    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                    "v_head_dim": 16},
            "rope_fraction": 1.0, "rope_theta": 10000.0, "norm": "rmsnorm",
            "act": "silu", "qkv_bias": False, "tie_embeddings": True,
            "max_seq_len": 512},
}
# The program's routed MoE FFN; no token is dropped while capacity_factor
# >= n_experts / top_k. Its equations are fixtures/equations/smoke.py. It
# runs in float32. In bfloat16, over the same 600 served tokens a seed (15
# seeds), sound runs read mean gaps of 4.0e-5 to 1.1e-3 and the int8
# control 5.3e-4 to 3.6e-3; at the settled rows alone (router swaps within
# rounding left out) 3.5e-6 to 6.5e-5 on 14 seeds but 4.8e-4 on one, where
# swaps at earlier positions reach later ones through attention, against
# the control's 1.9e-4 to 2.2e-3: at this size no limit holds in bfloat16.
SMOKE_MODELS["moe"] = dict(SMOKE_MODELS["attn"], name="moe-smoke", family="moe",
                           pattern=[{"kind": "attn", "moe": True}],
                           moe={"n_experts": 4, "top_k": 2, "capacity_factor": 2.0},
                           param_dtype="float32", compute_dtype="float32")
EQUATIONS = HERE / "fixtures" / "equations"


def make_root(tmp: Path, *, kind: str = "attn", loop: str = "closed",
              limit: float = 1.0, per_layer=()) -> Path:
    """A checkout-like directory holding one smoke cell, ``smoke.<loop>``,
    built from files alone; the metric readers are the benchmark's own."""
    base = tmp / "chipbench"
    for d in ("configs", "traffic", "limits"):
        (base / d).mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", base / "metrics")
    (base / "configs" / "smoke.json").write_text(json.dumps(
        {"engine": "pallas", "model": SMOKE_MODELS[kind]}))
    mix = {"max_slots": 3, "max_len": 256,
           "prompt_len": {"values": [16, 40], "weights": [0.5, 0.5]},
           "output_len": {"uniform": [6, 12]}}
    mix.update({"loop": "closed", "clients": 3} if loop == "closed"
               else {"loop": "open", "rate": 4.0})
    (base / "traffic" / f"{loop}.json").write_text(json.dumps(mix))
    cell = f"smoke.{loop}"
    (base / "limits" / f"{cell}.json").write_text(json.dumps(
        {"token_gap_mean": limit, "sample": 3, "min_tokens": 10}))
    e2e = [{"name": n, "unit": u, "better": "lower", "bound": 0.25,
            "source": "host_clock"}
           for n, u in (("itl_p95_ms", "ms"), ("tokens_per_s", "tokens/s"),
                        ("setup_s", "s"))]
    (tmp / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "smoke", "file": "chipbench/configs/smoke.json"}],
        "workloads": [{"name": cell, "config": "smoke", "traffic": loop,
                       "chips": 1}],
        "end_to_end": e2e, "per_layer": list(per_layer)}))
    return tmp


@pytest.fixture
def smoke_root(tmp_path):
    return lambda **kw: make_root(tmp_path, **kw)


def int8_control(m: dict, eq, limit: float) -> tuple[bool, dict]:
    """The int8 control of the reference of the equations ``eq``, put in
    the program's place on one finished request of 240 served tokens
    (seeded weights 1), judged by the check's own comparison under
    ``limit``."""
    from types import SimpleNamespace

    import numpy as np

    from cbench import check, weights
    from cbench.harness import program_config
    from cbench.loop import Req
    from cbench.traffic import Draw
    from repro.core.engine import ArcaneEngine
    from repro.models.transformer import LM
    shapes = LM(program_config(m), ArcaneEngine("ref")).param_shapes()
    params = weights.make_params(shapes, 1)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 256, 16).astype(np.int32)
    handle = SimpleNamespace(uid=0, done=True,
                             out_tokens=rng.integers(0, 256, 240).tolist())
    reqs = [Req(Draw(prompt, 240, 0.0), handle, 0.0, 0.0, slot=0)]
    mix = {"max_len": 256, "output_len": {"uniform": [240, 240]}}
    limits = {"token_gap_mean": limit, "sample": 1, "min_tokens": 100}
    _, ctl = check.run_check(eq, m, params, reqs, 2**31 + 99, mix, limits,
                             control=True)
    return ctl
