#!/usr/bin/env python3
"""Record ``fixtures/smoke_serve.xplane.pb.gz`` and its steps on a TPU.

The smoke model of ``fixtures/smoke_decode.steps.json`` (2 layers, width
64) with the Pallas kernels, served by ``ServeSession`` at 3 slots under a
closed loop of 3 clients (prompts of 16 and 40 tokens, 6-12 new tokens),
traced for a tenth of a second by the serving loop as a traced benchmark
run would be. Run from the root of a checkout, on the chip::

    python3 chipbench/tests/record_serve_trace.py
"""
import glob
import gzip
import json
import os
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[1] / "src")]

SEED = 2**31 + 90210
OUT = HERE / "fixtures" / "smoke_serve"
MIX = {"loop": "closed", "clients": 3, "max_slots": 3, "max_len": 256,
       "prompt_len": {"values": [16, 40], "weights": [0.5, 0.5]},
       "output_len": {"uniform": [6, 12]}}


def main() -> int:
    import jax

    from cbench import harness, loop, traffic, weights
    from repro.core.engine import ArcaneEngine
    from repro.models.transformer import LM
    from repro.serving.engine import ServeSession

    if jax.devices()[0].platform != "tpu":
        print("record_serve_trace: needs a TPU", file=sys.stderr)
        return 1
    model_cfg = json.loads(
        (HERE / "fixtures" / "smoke_decode.steps.json").read_text())["model"]
    model = LM(harness.program_config(model_cfg), ArcaneEngine("pallas"))
    params = weights.make_params(model.param_shapes(), SEED)
    session = ServeSession(model, params, max_slots=MIX["max_slots"],
                           max_len=MIX["max_len"])
    vocab = model_cfg["vocab"]
    harness.warm(session, MIX, SEED, vocab)
    lp = loop.Loop(session, MIX, traffic.Stream(MIX, SEED, vocab))
    lp.preroll()
    with tempfile.TemporaryDirectory() as d:
        lp.run(0.6, trace=(0.3, 0.1, lambda: jax.profiler.start_trace(d),
                           jax.profiler.stop_trace))
        pb = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                              recursive=True), key=os.path.getmtime)[-1]
        data = gzip.compress(Path(pb).read_bytes(), 9)
    Path(f"{OUT}.xplane.pb.gz").write_bytes(data)
    steps = [[s.t0, s.t1, s.live, s.prefill_lens, s.decode_lens, s.traced]
             for s in lp.steps]
    Path(f"{OUT}.steps.json").write_text(json.dumps(
        {"steps": steps, "max_slots": MIX["max_slots"], "model": model_cfg}))
    print(f"record_serve_trace: {len(data)} bytes, "
          f"{sum(s[5] for s in steps)} traced steps")
    return 0


if __name__ == "__main__":
    sys.exit(main())
