#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip.

    python chipbench/sweep.py --workload <cell> --seconds <s> --seed <n> --rates <r> [<r> ...]

One set-up, then for each offered rate (requests/s) a window of the cell's
mix at that rate, drained before the next. Prints one JSON line per rate:
requests offered and completed per second, TTFT p50/p95 and the backlog
(pending + live) at the close. The knee is the highest rate whose
completions keep up and whose backlog does not grow.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from cbench import derive, harness, loop, spec, traffic
    from cbench.stats import percentile
    cell = spec.load(ROOT, args.workload)
    m = cell.config["model"]
    _, session = harness.build(cell, args.seed)
    harness.warm(session, cell.mix, args.seed, m["vocab"])
    for rate in args.rates:
        mix = dict(cell.mix, rate=rate)
        draws = traffic.Stream(mix, args.seed, m["vocab"])
        lp = loop.Loop(session, mix, draws)
        t0, t1 = lp.run(args.seconds)
        ctx = derive.Context(model=m, mix=mix, reqs=lp.reqs, steps=lp.steps,
                             window=(t0, t1), setup_s=0.0, compiles_in_window=0,
                             peaks=None, equations=cell.equations)
        ttft = derive.ttfts_s(ctx)
        done = sum(1 for r in lp.reqs if r.handle.done and r.times[-1] <= t1)
        print(json.dumps({
            "rate": rate, "offered_per_s": len(derive.due_in_window(ctx)) / (t1 - t0),
            "completed_per_s": done / (t1 - t0),
            "ttft_p50_ms": 1e3 * percentile(ttft, 50), "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "backlog_at_close": len(session.pending) + sum(s is not None for s in session.slots)}),
            flush=True)
        while session.pending or any(s is not None for s in session.slots):
            session.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
