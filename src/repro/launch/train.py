"""Training launcher: mesh setup, sharded state, fault-tolerant loop.

Fault-tolerance machinery (single-host shapes of the multi-pod mechanisms):

  * **checkpoint/restart** — CheckpointManager (atomic, async, elastic);
    resume is automatic from <ckpt_dir>/LATEST, and the data pipeline
    regenerates the exact stream from the step counter alone.
  * **preemption handling** — SIGTERM/SIGINT trigger a synchronous save at
    the next step boundary before exit (the TPU preemption-notice pattern).
  * **step watchdog** — a straggler/hang detector: if a step exceeds
    ``watchdog_factor`` × the trailing median, the event is logged with the
    step number (at pod scale this feeds the reschedule/elastic controller;
    here it is surfaced in metrics and the log).

Backend: training runs the blocked-jnp ``ref`` path, asked for explicitly
with ``--backend ref``. The default ``pallas`` is refused, for two reasons
found by compiling the step for a v5e:2x2 topology: the Pallas kernels define
no backward pass (``pallas_call`` has no JVP for them), and XLA cannot
partition a Mosaic kernel inside a program sharded over several devices.

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
        --smoke --steps 50 --batch 8 --seq 128 --backend ref
"""
from __future__ import annotations

import argparse
import signal
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.core.engine import ArcaneEngine
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.distributed.sharding import (batch_pspecs, param_pspecs,
                                        to_shardings, zero_pspecs)
from repro.launch.chip import BACKENDS, use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.transformer import LM
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.train.step import make_train_step


PALLAS_TRAIN_REFUSAL = (
    "training cannot run --backend pallas: the Pallas kernels define no "
    "backward pass, and XLA cannot partition a Mosaic kernel over a mesh; "
    "pass --backend ref")


class Preemption:
    def __init__(self):
        self.flag = False
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, self._handler)
            except ValueError:
                pass  # not main thread

    def _handler(self, signum, frame):
        self.flag = True


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--watchdog-factor", type=float, default=5.0)
    ap.add_argument("--backend", choices=BACKENDS, default="pallas")
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args(argv)
    if args.backend == "pallas":
        raise SystemExit(PALLAS_TRAIN_REFUSAL)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    engine = ArcaneEngine(backend=args.backend)
    model = LM(cfg, engine)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(args.model_axis))

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                          total_steps=args.steps)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)
    source = SyntheticLM(data_cfg)

    with mesh:
        # jitted inits write straight into their shardings: no device ever
        # holds the whole model or its optimizer state
        p_shapes = model.param_shapes()
        p_sh = to_shardings(param_pspecs(p_shapes, mesh), mesh)
        o_sh = to_shardings(zero_pspecs(
            jax.eval_shape(lambda p: adamw_init(opt_cfg, p), p_shapes),
            mesh), mesh)
        params = jax.jit(model.init_params, out_shardings=p_sh)(
            jax.random.key(0))
        opt_state = jax.jit(lambda p: adamw_init(opt_cfg, p),
                            out_shardings=o_sh)(params)
        step_fn = jax.jit(
            make_train_step(model, opt_cfg, microbatches=args.microbatches),
            in_shardings=(p_sh, o_sh, None),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1))

        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start_step = 0
        if ckpt is not None and ckpt.latest_step() is not None:
            start_step = ckpt.latest_step()
            state, extra = ckpt.restore(
                start_step, {"params": params, "opt": opt_state},
                shardings={"params": p_sh, "opt": o_sh})
            params, opt_state = state["params"], state["opt"]
            print(f"[resume] from step {start_step}")

        preempt = Preemption()
        durations: list[float] = []
        stragglers = 0
        history = []
        it = Prefetcher(source, start_step=start_step)
        for step in range(start_step, args.steps):
            batch_np = next(it)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            durations.append(dt)
            if len(durations) > 8:
                med = statistics.median(durations[-32:])
                if dt > args.watchdog_factor * med:
                    stragglers += 1
                    print(f"[watchdog] step {step}: {dt:.2f}s vs median "
                          f"{med:.2f}s — straggler/hang suspected")
            history.append(loss)
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} {dt:.2f}s")
            should_save = ckpt is not None and (
                (step + 1) % args.ckpt_every == 0 or preempt.flag
                or step == args.steps - 1)
            if should_save:
                ckpt.save(step + 1, {"params": params, "opt": opt_state},
                          extra={"loss": loss}, blocking=preempt.flag)
            if preempt.flag:
                print(f"[preempt] checkpoint at step {step + 1}, exiting")
                break
        it.close()
        if ckpt is not None:
            ckpt.wait()
    return {"history": history, "stragglers": stragglers,
            "final_loss": history[-1] if history else None}


if __name__ == "__main__":
    run()
