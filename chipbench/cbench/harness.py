"""One run of one cell: set-up, the measured window, the check, the metrics.

``run_cell`` is the whole run after the platform check; ``run.py`` calls it
on a TPU and the CPU tests call it at smoke sizes.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
import types
import typing

import jax

from cbench import check, derive, loop, spec, traffic, tracing, weights
from cbench.compilelog import CompileLog
from cbench.peaks import chip_peaks

TRACE_AT = 0.3        # the trace starts this far into the window
TRACE_S = 3.0         # and lasts this long, at most 40% of the window


def program_config(m: dict):
    """The program's ``ModelConfig`` from a configuration's ``model`` block.
    Each field is built as ``ModelConfig``'s own annotation says, so a
    sub-configuration (``moe``, ``mla``, a tuple of ``LayerSpec``) arrives
    as its dataclass, whichever the program has."""
    from repro.configs.base import ModelConfig
    return _typed(ModelConfig, m)


def _typed(tp, v):
    """``v`` (parsed JSON) as the annotated type ``tp``: dataclasses from
    dicts, tuples from lists, through ``Optional``."""
    if v is None:
        return None
    if dataclasses.is_dataclass(tp):
        hints = typing.get_type_hints(tp)
        return tp(**{k: _typed(hints[k], x) for k, x in v.items()})
    origin, args = typing.get_origin(tp), typing.get_args(tp)
    if origin in (typing.Union, types.UnionType):
        return _typed(next(a for a in args if a is not type(None)), v)
    if origin is tuple:
        return tuple(_typed(args[0], x) for x in v)
    return v


def build(cell: spec.Cell, seed: int):
    """The benchmark's seeded weights and a session serving them."""
    from repro.core.engine import ArcaneEngine
    from repro.models.transformer import LM
    from repro.serving.engine import ServeSession
    model = LM(program_config(cell.config["model"]),
               ArcaneEngine(cell.config["engine"]))
    params = weights.make_params(model.param_shapes(), seed)
    mix = cell.mix
    session = ServeSession(model, params, max_slots=mix["max_slots"],
                           max_len=mix["max_len"], seed=seed & 0x7FFFFFFF)
    return params, session


def warm(session, mix: dict, seed: int, vocab: int) -> None:
    """Compile every shape the mix will use: a batch-1 prefill per prompt
    length, the decode at the session's slots, the admission copy."""
    rng = traffic.rng_for(seed, 6)
    lens = traffic.sizes(mix["prompt_len"])
    for i in range(0, len(lens), mix["max_slots"]):
        reqs = [session.submit(rng.integers(0, vocab, n, dtype="int32"),
                               max_new_tokens=2)
                for n in lens[i: i + mix["max_slots"]]]
        while not all(r.done for r in reqs):
            session.step()


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, control: bool = False) -> dict:
    """One run; returns the result line's object. ``control`` also judges
    the int8 control's picks on the same sample, under ``"control"``
    (calibration only: the benchmark's own runs never run it)."""
    compiles = CompileLog()
    m, mix = cell.config["model"], cell.mix
    params, session = build(cell, seed)
    draws = traffic.Stream(mix, seed, m["vocab"])
    warm(session, mix, seed, m["vocab"])
    lp = loop.Loop(session, mix, draws)
    if mix["loop"] == "closed":
        lp.preroll()
    gc.collect()
    n_compiled = compiles.count
    setup_s = time.perf_counter() - t_start
    print(f"chipbench: set-up {setup_s:.3f} s, {compiles.count} compiles "
          f"({compiles.seconds:.3f} s), {compiles.cache_hits} cache hits",
          file=sys.stderr, flush=True)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    plan = None
    if trace:
        plan = (TRACE_AT * seconds, min(TRACE_S, 0.4 * seconds),
                lambda: jax.profiler.start_trace(trace_dir),
                jax.profiler.stop_trace)
    lp.run(seconds, trace=plan)
    in_window = compiles.count - n_compiled

    dev = jax.devices()[: cell.chips]
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in dev)
    tr = None
    if trace:
        try:
            tr = tracing.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # free the program's state before the reference runs
    lp.session = None
    del session
    gc.collect()
    t_check = time.perf_counter()
    (correct, numbers), *ctl = check.run_check(
        cell.equations, m, params, lp.reqs, seed, mix, cell.limits,
        control=control)
    print(f"chipbench: check {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr, flush=True)

    try:
        peaks = chip_peaks(dev[0].device_kind)
    except ValueError:
        peaks = None
    ctx = derive.Context(model=m, mix=mix, reqs=lp.reqs, steps=lp.steps,
                         window=lp.window, setup_s=setup_s,
                         compiles_in_window=in_window, peaks=peaks, trace=tr,
                         equations=cell.equations)
    metrics = {}
    for entry in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(cell.root, entry["name"])(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_mem}
    out = {"correct": correct,
           "attempted": len(derive.due_in_window(ctx)),
           "failed": 0,
           "metrics": metrics,
           "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
    if ctl:
        out["control"] = {"correct": ctl[0][0], "check": _named(ctl[0][1])}
    out["check"] = _named(numbers)
    return out


def _named(numbers: dict) -> dict:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
