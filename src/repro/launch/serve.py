"""Serving launcher: batched continuous decoding on one device.

Runs the Pallas kernels on a TPU by default; anywhere else it fails unless
``--backend ref`` is given (see ``repro.launch.chip``). Usage::

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --requests 8 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-32b --smoke \
        --backend ref      # CPU
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.engine import ArcaneEngine
from repro.launch.chip import BACKENDS, check_backend, use_compile_cache
from repro.models.transformer import LM
from repro.serving.engine import ServeSession


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--backend", choices=BACKENDS, default="pallas")
    args = ap.parse_args(argv)
    check_backend(args.backend)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = LM(cfg, ArcaneEngine(backend=args.backend))
    # one compiled init program instead of an eager op per weight
    params = jax.jit(model.init_params)(jax.random.key(0))
    sess = ServeSession(model, params, max_slots=args.slots,
                        max_len=args.max_len)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        sess.submit(rng.integers(0, cfg.vocab, plen),
                    max_new_tokens=args.max_new)
    t0 = time.perf_counter()
    done = sess.run_to_completion()
    dt = time.perf_counter() - t0
    tokens = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {tokens} tokens in {dt:.2f}s "
          f"({tokens / dt:.1f} tok/s)")
    return {"requests": len(done), "tokens": tokens, "seconds": dt}


if __name__ == "__main__":
    run()
