#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, traffic mix,
limits and metric readers are found by name from ``BENCHMARK.json`` (see
``cbench.spec``). One process: set-up (seeded weights made on the device,
every shape of the mix compiled, from JAX's persistent cache at
``$JAX_COMPILATION_CACHE_DIR`` or else ``<checkout>/.jax_cache``), then
``--seconds`` of traffic through ``ServeSession``, then the output check
against the float32 reference. With ``--trace 1`` a few seconds of the
window are traced and the per-layer metrics are reported instead of the
end-to-end ones.

Anything but a TPU with as many chips as the cell asks for exits nonzero
with no result line. The last line of standard output is one JSON object;
the numbers the check compared close standard error, each with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    from cbench import spec
    cell = spec.load(ROOT, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX has {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from cbench.harness import run_cell
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(out), flush=True)
    for k, v in out["check"].items():
        print(f"chipbench check: {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
