#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, in one process.

    python chipbench/calibrate.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]
        [--fault stale-cache|token-altered] [--rate <requests/s>]

For each seed: a full run of the cell (weights, traffic and window from
that seed, the compiled programs shared), judged as the benchmark judges
it, and the int8 control (the cell's own reference, ``logits_at`` of its
``spec.Equations`` with ``quant``) put in the program's place on the same
sample, judged by the same comparison. The limit goes between the largest
program reading and the smallest control reading. ``--fault`` plants one of
``cbench.faults`` in the program first, at the cell's own size;
``--rate`` offers an open-loop cell another rate. One JSON line per seed;
needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from cbench import faults, spec
    from cbench.harness import run_cell
    from repro.models.transformer import LM
    cell = spec.load(ROOT, args.workload)
    if args.rate is not None:
        cell.mix = dict(cell.mix, rate=args.rate)
    if args.fault:
        LM.decode_step = faults.faulty_decode_step(args.fault)
    for seed in args.seeds:
        out = run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                       control=True)
        print(json.dumps({
            "seed": seed, "fault": args.fault, "rate": cell.mix.get("rate"),
            "correct": out["correct"], **values(out["check"]),
            "control_correct": out["control"]["correct"],
            "control": values(out["control"]["check"]),
            "attempted": out["attempted"],
            "metrics": values(out["metrics"]),
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}), flush=True)
    return 0


def values(check: dict) -> dict:
    return {k: v["value"] for k, v in check.items()}


if __name__ == "__main__":
    sys.exit(main())
