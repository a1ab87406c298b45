"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
host devices stand in for 2 TPU v5e pods. For each cell the step function is
lowered with ShapeDtypeStruct inputs (no allocation), compiled, and the
memory/cost analysis + the collective-byte census (parsed from the compiled
HLO) are recorded for EXPERIMENTS §Dry-run and §Roofline.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
        --shape train_4k --mesh both --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
"""
# The VERY FIRST lines — before ANY other import — jax locks the device
# count on first init.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse   # noqa: E402
import json       # noqa: E402
import re         # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_config, grid  # noqa: E402
from repro.core.engine import ArcaneEngine  # noqa: E402
from repro.distributed.sharding import (batch_pspecs, cache_pspecs,  # noqa: E402
                                        param_pspecs, to_shardings,
                                        zero_pspecs)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import (FSDP_ARCHS, cache_specs, input_specs,  # noqa: E402
                                opt_config_for, state_specs)
from repro.models.transformer import LM  # noqa: E402
from repro.train.step import make_serve_steps, make_train_step  # noqa: E402

_SHAPE_RE = re.compile(r"(bf16|f32|f16|s32|s8|u32|u8|pred|f64|s64|u64|s16|u16)"
                       r"\[([0-9,]*)\]")

_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
          "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
          "pred": 1}

# ``%name = <shape> all-reduce(...)`` — also match async -start forms,
# skip -done (would double count).
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output-shape bytes of every collective op in the compiled HLO.

    The output shape bytes approximate what crosses the wire per device for
    AG/AR/RS/A2A/CP, up to the ring-algorithm factor (folded into the
    roofline link constant). NOTE: ops inside while-loop (scan) bodies appear
    once — the dry-run corrects by depth extrapolation (see lower_cell).
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(2).lower()
        out[op] = out.get(op, 0) + _shape_bytes(m.group(1))
    return out


def extrapolate(full: dict, p1: dict, p2: dict, n_periods: int) -> dict:
    """Correct scan-body single-count: X(L) = X(1) + (L-1)·(X(2)-X(1)).

    Exact for quantities linear in depth (flops, bytes, collective bytes,
    optimizer update work); `full` supplies everything else (peak memory).
    """
    def lin(a, b):
        return a + (n_periods - 1) * (b - a)

    coll = {}
    for k in set(p1["collective_bytes"]) | set(p2["collective_bytes"]):
        coll[k] = int(lin(p1["collective_bytes"].get(k, 0),
                          p2["collective_bytes"].get(k, 0)))
    return {
        "flops": float(lin(p1["flops"], p2["flops"])),
        "bytes_accessed": float(lin(p1["bytes_accessed"],
                                    p2["bytes_accessed"])),
        "collective_bytes": coll,
    }


def lower_cell(arch: str, shape_name: str, mesh, *,
               n_periods: int | None = None, constrain_acts: bool = False,
               cfg_overrides: dict | None = None):
    """Lower+compile one cell; returns the result record.

    ``n_periods`` overrides the depth (in pattern periods) — used by the
    depth-extrapolation that corrects cost_analysis's once-per-scan counting.
    """
    import dataclasses as _dc
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    if n_periods is not None:
        repl = {"n_layers": cfg.n_dense_layers + n_periods * cfg.period}
        if cfg.enc_dec:
            repl["n_enc_layers"] = n_periods
        cfg = _dc.replace(cfg, **repl)
    shape = SHAPES[shape_name]
    # XLA cannot partition a Mosaic kernel over a mesh, so the sharded
    # dry-run lowers the partitionable blocked-jnp path
    engine = ArcaneEngine(backend="ref")
    model = LM(cfg, engine, unroll=n_periods is not None)
    from repro.distributed.sharding import set_activation_mesh
    set_activation_mesh(mesh if constrain_acts else None)
    fsdp = arch in FSDP_ARCHS
    specs = input_specs(arch, shape, model)
    t0 = time.time()

    with mesh:
        p_sh = to_shardings(param_pspecs(specs["params"], mesh, fsdp=fsdp),
                            mesh)
        b_sh = to_shardings(batch_pspecs(specs["batch"], mesh), mesh)
        if shape.kind == "train":
            opt_cfg = opt_config_for(arch)
            o_sh = to_shardings(zero_pspecs(specs["opt_state"], mesh), mesh)
            g_sh = to_shardings(zero_pspecs(specs["params"], mesh), mesh) \
                if constrain_acts else None
            step = make_train_step(model, opt_cfg, grad_shardings=g_sh)
            fn = jax.jit(step,
                         in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
            lowered = fn.lower(specs["params"], specs["opt_state"],
                               specs["batch"])
        elif shape.kind == "prefill":
            prefill_step, _ = make_serve_steps(
                model, enc_len=shape.seq_len if cfg.enc_dec else 0)
            c_sh = to_shardings(cache_pspecs(specs["cache"], mesh), mesh)
            fn = jax.jit(prefill_step,
                         in_shardings=(p_sh, b_sh, c_sh),
                         out_shardings=(None, c_sh),
                         donate_argnums=(2,))
            lowered = fn.lower(specs["params"], specs["batch"],
                               specs["cache"])
        else:
            _, decode_step = make_serve_steps(
                model, enc_len=shape.seq_len if cfg.enc_dec else 0)
            c_sh = to_shardings(cache_pspecs(specs["cache"], mesh), mesh)
            fn = jax.jit(decode_step,
                         in_shardings=(p_sh, b_sh["tokens"], b_sh["position"],
                                       c_sh),
                         out_shardings=(None, c_sh),
                         donate_argnums=(3,))
            lowered = fn.lower(specs["params"], specs["batch"]["tokens"],
                               specs["batch"]["position"], specs["cache"])
        compiled = lowered.compile()
    set_activation_mesh(None)

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes(hlo)
    n_dev = mesh.devices.size
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": int(n_dev),
        "seconds_to_compile": round(time.time() - t0, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(mem.peak_memory_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        },
        "model": {
            "params": get_config(arch).param_count(),
            "active_params": get_config(arch).active_param_count(),
        },
    }
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch x shape) cell")
    ap.add_argument("--no-roofline", action="store_true",
                    help="skip the 1/2-period extrapolation compiles")
    ap.add_argument("--constrain-acts", action="store_true",
                    help="apply activation sharding constraints (§Perf)")
    ap.add_argument("--ring-local-cache", action="store_true",
                    help="window-sized ring KV cache for local layers")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in ARCHS:
            for sh in grid(arch):
                cells.append((arch, sh.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape_name in cells:
        for mesh_name, mesh in meshes:
            tag = f"{arch}__{shape_name}__{mesh_name}"
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path):
                print(f"[skip] {tag}")
                continue
            try:
                ov = ({"ring_local_cache": True}
                      if args.ring_local_cache else None)
                rec = lower_cell(arch, shape_name, mesh,
                                 constrain_acts=args.constrain_acts,
                                 cfg_overrides=ov)
                if mesh_name == "single" and not args.no_roofline:
                    # depth extrapolation: correct once-per-scan counting
                    cfgK = get_config(arch)
                    p1 = lower_cell(arch, shape_name, mesh,
                                    n_periods=1,
                                    constrain_acts=args.constrain_acts,
                                    cfg_overrides=ov)
                    p2 = lower_cell(arch, shape_name, mesh,
                                    n_periods=2,
                                    constrain_acts=args.constrain_acts,
                                    cfg_overrides=ov)
                    rec["corrected"] = extrapolate(rec, p1, p2,
                                                   cfgK.n_periods)
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                peak = rec["memory"]["peak_bytes"] / 2**30
                arg = rec["memory"]["argument_bytes"] / 2**30
                cf = rec.get("corrected", rec)
                print(f"[ok]   {tag}: compile={rec['seconds_to_compile']}s "
                      f"flops={cf['flops']:.3e} peak/dev={peak:.2f}GiB "
                      f"args/dev={arg:.2f}GiB "
                      f"coll/dev={sum(cf['collective_bytes'].values())/2**20:.1f}MiB")
            except Exception as e:
                failures += 1
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
