#!/usr/bin/env python3
"""Smoke test of the main paths on a TPU: serving on one chip, training on four.

One chip (no arguments) serves stablelm-3b at its published widths, all 32
layers, with random weights from seed 0, through the path a user calls:
``ArcaneEngine("pallas")`` -> ``LM.prefill`` / ``LM.decode_step`` ->
``ServeSession``. Phases:

1. JAX's platform is a TPU; anything else exits nonzero with no result.
2. Eight requests (prompt lengths 77, 256 and 500; 32 new tokens each) are
   served at 4 slots and max_len 2048, and every one finishes.
3. The compiled prefill and decode programs hold Mosaic kernels
   (``tpu_custom_call``).
4. One request's prefill logits and 4 teacher-forced decode-step logits
   agree with the ``ref`` engine on the same parameters.

``--chips 4`` runs only the sharded training path and what it is compared
with: one step at stablelm-3b widths and 2 layers on a (data 2, model 2)
mesh against the same step on one device, then 3 full-depth steps through
``repro.launch.train``. Training runs the ``ref`` backend, because the
launcher refuses ``pallas`` (see ``repro.launch.train``).

A failed phase raises, so the process exits nonzero. The last line of
standard output is ``{"ok": true, "device": {...}}``. Run from the repo
root::

    python3 chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "stablelm-3b"

# serving phase
N_REQUESTS = 8
PROMPT_LENS = (77, 256, 500)
MAX_NEW = 32
SLOTS = 4
MAX_LEN = 2048

# comparison phase: pallas vs ref logits of one request. Both engines keep
# activations in bf16 between ops and accumulate in f32 inside each one;
# they differ only in blocking and accumulation order, so they disagree by
# bf16 roundings (2^-9 relative each) carried through 32 residual layers.
COMPARE_PROMPT = 256
COMPARE_STEPS = 4
# Relative RMS error of each logits row against ref. A few roundings per
# layer, random-walking over 32 layers, come to 1-2% (1.6% measured on a
# TPU v5e); a real fault (wrong mask, position or cache slot) moves logits
# by their own scale (~1).
REL_RMS_TOL = 0.05
# Same greedy token in every row is not required (random weights give
# near-ties), but the worst single logit must stay well inside the logits'
# spread: 0.25 of the reference row's standard deviation.
MAX_ABS_TOL_SD = 0.25

# four-chip phase: sharded vs one-device training step at 2 layers. The
# mesh splits reductions (all-reduce over "model", the data-parallel
# gradient sum) but computes the same math in bf16/f32.
TRAIN_LAYERS = 2
TRAIN_BATCH = 8
TRAIN_SEQ = 256
# The loss is a mean of ~2k per-token f32 log-likelihoods over bf16 logits:
# reordering moves it by far less than 1e-3 of its value (~10.8).
LOSS_REL_TOL = 1e-3
# The gradient norm sums squares of bf16 gradients whose partial sums are
# taken in another order on the mesh: a few bf16 roundings, 1% is ample.
GNORM_REL_TOL = 1e-2
FULL_STEPS = 3
FULL_BATCH = 4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def _gb(tree) -> float:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree)) / 1e9


class CompileLog:
    """Counts backend compiles and persistent-cache hits in this process."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def build(cfg, engine, seed: int = 0):
    """LM + parameters from one jitted init (one program, not an op per weight)."""
    import jax
    from repro.models.transformer import LM
    model = LM(cfg, engine)
    return model, jax.jit(model.init_params)(jax.random.key(seed))


def serve(model, params, *, n_requests: int = N_REQUESTS,
          prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW,
          slots: int = SLOTS, max_len: int = MAX_LEN, seed: int = 0) -> dict:
    """Serve ``n_requests`` through ``ServeSession``; checks every finish."""
    from repro.serving.engine import ServeSession
    vocab = model.cfg.vocab
    sess = ServeSession(model, params, max_slots=slots, max_len=max_len)
    rng = np.random.default_rng(seed)
    reqs = [sess.submit(rng.integers(0, vocab, prompt_lens[i % len(prompt_lens)]),
                        max_new_tokens=max_new)
            for i in range(n_requests)]
    t0 = time.perf_counter()
    done = sess.run_to_completion()
    seconds = time.perf_counter() - t0
    if len(done) != n_requests or any(not r.done for r in reqs):
        raise SmokeFailure(f"{len(done)}/{n_requests} requests finished")
    for r in reqs:
        toks = np.asarray(r.out_tokens)
        if len(toks) != max_new or toks.min() < 0 or toks.max() >= vocab:
            raise SmokeFailure(f"request {r.uid}: bad tokens {r.out_tokens}")
    return {"requests": len(done), "slots": slots,
            "tokens": sum(len(r.out_tokens) for r in done),
            "cache_gb": _gb(sess.cache), "seconds": seconds}


def kernel_counts(model, params, *, prompt_len: int = PROMPT_LENS[1],
                  slots: int = SLOTS, max_len: int = MAX_LEN) -> dict:
    """``tpu_custom_call`` count in the compiled prefill and decode.

    Compiles what ``ServeSession`` compiles (``jax.jit`` of the model's own
    methods at the served shapes), so the persistent cache serves it."""
    import jax
    import jax.numpy as jnp
    tok = jax.ShapeDtypeStruct
    prefill = jax.jit(model.prefill).lower(
        params, {"tokens": tok((1, prompt_len), jnp.int32)},
        model.cache_shapes(1, max_len)).compile().as_text()
    decode = jax.jit(model.decode_step).lower(
        params, tok((slots,), jnp.int32), tok((slots,), jnp.int32),
        model.cache_shapes(slots, max_len)).compile().as_text()
    return {"prefill": prefill.count("tpu_custom_call"),
            "decode": decode.count("tpu_custom_call")}


def teacher_forced_logits(model, params, prompt, forced, max_len: int):
    """Prefill logits then one decode step per forced token: (1+n, V) f32."""
    import jax
    import jax.numpy as jnp
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt[None])},
                            model.init_cache(1, max_len))
    rows = [logits]
    for i, t in enumerate(forced):
        logits, cache = decode(params, jnp.asarray([t], jnp.int32),
                               jnp.asarray([len(prompt) + i], jnp.int32),
                               cache)
        rows.append(logits)
    return np.stack([np.asarray(r, np.float32)[0] for r in rows])


def compare(model, ref_model, params, *, prompt_len: int = COMPARE_PROMPT,
            steps: int = COMPARE_STEPS, max_len: int = MAX_LEN,
            seed: int = 1) -> dict:
    """Logits of ``model`` vs ``ref_model`` (same params); checks tolerances."""
    rng = np.random.default_rng(seed)
    vocab = model.cfg.vocab
    prompt = rng.integers(0, vocab, prompt_len).astype(np.int32)
    forced = rng.integers(0, vocab, steps).astype(np.int32)
    got = teacher_forced_logits(model, params, prompt, forced, max_len)
    want = teacher_forced_logits(ref_model, params, prompt, forced, max_len)
    if not np.isfinite(got).all():
        raise SmokeFailure("non-finite logits")
    err = np.abs(got - want)
    rel_rms = np.sqrt((err ** 2).mean(-1) / (want ** 2).mean(-1))
    max_sd = err.max(-1) / want.std(-1)
    out = {"max_abs_err": float(err.max()),
           "rel_rms_err": [float(x) for x in rel_rms],
           "max_err_over_sd": [float(x) for x in max_sd],
           "argmax_agree": int((got.argmax(-1) == want.argmax(-1)).sum()),
           "rows": len(got)}
    if (rel_rms > REL_RMS_TOL).any() or (max_sd > MAX_ABS_TOL_SD).any():
        raise SmokeFailure(f"pallas vs ref logits beyond tolerance: {out}")
    return out


def say(key: str, value) -> None:
    print(f"chip_smoke: {key} = {value}", flush=True)


def device_memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit") if k in stats}


def run_one_chip() -> None:
    import jax
    from repro.configs import get_config
    from repro.core.engine import ArcaneEngine
    from repro.models.transformer import LM

    compiles = CompileLog()
    cfg = get_config(ARCH)
    engine = ArcaneEngine(backend="pallas")
    model, params = build(cfg, engine)
    say("backend", engine.backend)
    say("config", f"{cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads} head_dim={cfg.resolved_head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab} dtype={cfg.param_dtype}")
    say("param_gb", _gb(params))

    served = serve(model, params)
    say("cache_gb", served["cache_gb"])
    say("served", f"{served['requests']}/{N_REQUESTS} requests, "
        f"{served['tokens']} tokens, slots={served['slots']}, "
        f"wall {served['seconds']:.3f} s including compiles")

    counts = kernel_counts(model, params)
    say("tpu_custom_call", counts)
    if counts["prefill"] == 0 or counts["decode"] == 0:
        raise SmokeFailure(f"no Mosaic kernel in the compiled programs: {counts}")

    ref_model = LM(cfg, ArcaneEngine(backend="ref"))
    cmp = compare(model, ref_model, params)
    say("max_logit_err", cmp["max_abs_err"])
    say("compare", cmp)
    say("compiles", f"{compiles.count} programs, {compiles.seconds:.3f} s, "
        f"{compiles.cache_hits} persistent-cache hits")
    say("memory", device_memory(jax.devices()[0]))


def train_compare() -> dict:
    """One training step: (data 2, model 2) mesh vs one device, 2 layers."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.engine import ArcaneEngine
    from repro.distributed.sharding import (batch_pspecs, param_pspecs,
                                            to_shardings, zero_pspecs)
    from repro.launch.mesh import make_host_mesh
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.step import make_train_step

    cfg = dataclasses.replace(get_config(ARCH), n_layers=TRAIN_LAYERS)
    model, params = build(cfg, ArcaneEngine(backend="ref"))
    opt_cfg = AdamWConfig(total_steps=1, warmup_steps=0)
    opt = jax.jit(functools.partial(adamw_init, opt_cfg))(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (TRAIN_BATCH, TRAIN_SEQ)), jnp.int32)}
    step = make_train_step(model, opt_cfg)
    _, _, m1 = jax.jit(step)(params, opt, batch)
    one = {k: float(m1[k]) for k in ("loss", "grad_norm")}

    mesh = make_host_mesh(model_axis=2)
    with mesh:
        p_sh = to_shardings(param_pspecs(params, mesh), mesh)
        o_sh = to_shardings(zero_pspecs(opt, mesh), mesh)
        b_sh = to_shardings(batch_pspecs(batch, mesh), mesh)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None))
        _, _, m4 = fn(jax.device_put(params, p_sh),
                      jax.device_put(opt, o_sh), jax.device_put(batch, b_sh))
    mesh_m = {k: float(m4[k]) for k in ("loss", "grad_norm")}
    rel = {k: abs(mesh_m[k] - one[k]) / abs(one[k]) for k in one}
    out = {"mesh": dict(mesh.shape), "one_device": one, "sharded": mesh_m,
           "rel_diff": rel}
    if not all(np.isfinite(v) for v in (*one.values(), *mesh_m.values())):
        raise SmokeFailure(f"non-finite training metrics: {out}")
    if rel["loss"] > LOSS_REL_TOL or rel["grad_norm"] > GNORM_REL_TOL:
        raise SmokeFailure(f"sharded step disagrees with one device: {out}")
    return out


def run_four_chips() -> None:
    import jax
    from repro.launch import train
    if len(jax.devices()) != 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    say("backend", f"ref (pallas refused by the training launcher: "
        f"{train.PALLAS_TRAIN_REFUSAL})")
    say("train_compare", train_compare())
    hist = train.run(["--arch", ARCH, "--steps", str(FULL_STEPS),
                      "--batch", str(FULL_BATCH), "--seq", str(TRAIN_SEQ),
                      "--model-axis", "2", "--backend", "ref"])["history"]
    if len(hist) != FULL_STEPS or not np.isfinite(hist).all():
        raise SmokeFailure(f"full-depth training history {hist}")
    say("full_depth_losses", hist)
    for d in jax.devices():
        say(f"memory[{d.id}]", device_memory(d))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run it "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's platform is {platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.chip import use_compile_cache
    say("compile_cache", use_compile_cache())
    dev = jax.devices()[0]
    say("device", f"{dev.platform} {dev.device_kind} x{len(jax.devices())}")
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
