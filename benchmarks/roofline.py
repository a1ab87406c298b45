"""Roofline extraction: dry-run artifacts → three-term analysis per cell.

    compute    = HLO_FLOPs / (chips × peak bf16 FLOP/s)
    memory     = HLO_bytes / (chips × peak HBM bytes/s)
    collective = Σ collective-bytes / (chips × ICI bytes/s per link)

with the peaks of the dry-run's target chip (``TARGET_DEVICE_KIND``) read
from ``repro.peaks``.

HLO_FLOPs / bytes come from compiled cost_analysis with the scan-depth
extrapolation (see launch/dryrun.py). Under SPMD the compiled module IS one
device's program, so cost_analysis flops/bytes and the collective census are
all PER-DEVICE quantities (verified against analytic per-device estimates in
EXPERIMENTS §Roofline-method): each term divides by a single chip's peak.

Caveats recorded with the numbers (EXPERIMENTS §Roofline): XLA:CPU fusion
differs from TPU, so the memory term is an upper bound — chunk buffers that a
TPU keeps in VMEM are counted as HBM traffic here; the collective census
ignores ring-algorithm factors (a ring all-gather of N bytes moves ~N bytes
per link regardless of participants, so output-shape bytes are the right
order).
"""
from __future__ import annotations

import glob
import json
import os

from repro.peaks import chip_peaks

# the dry-run models a v5e pod on placeholder devices
TARGET_DEVICE_KIND = "TPU v5 lite"


def load_cells(dryrun_dir: str, mesh: str = "single") -> list[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, f"*__{mesh}.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def analyze(rec: dict) -> dict:
    n = rec["n_devices"]
    c = rec.get("corrected", rec)
    flops = max(c["flops"], 0.0)          # per-device (SPMD module)
    byts = max(c["bytes_accessed"], 0.0)  # per-device
    coll = sum(max(v, 0) for v in c["collective_bytes"].values())
    peaks = chip_peaks(TARGET_DEVICE_KIND)
    t_compute = flops / peaks.bf16_flops
    t_memory = byts / peaks.hbm_bw
    t_coll = coll / peaks.ici_bw_per_link
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    # MODEL_FLOPS: 6·N·D for train (fwd+bwd), 2·N·D for single forward
    # (prefill), 2·N_active·D_tokens for decode (one token per sequence).
    shape = rec["shape"]
    na = rec["model"]["active_params"]
    if shape.startswith("train"):
        tokens = {"train_4k": 4096 * 256}[shape]
        model_flops = 6 * na * tokens
    elif shape.startswith("prefill"):
        tokens = 32768 * 32
        model_flops = 2 * na * tokens
    else:
        tokens = {"decode_32k": 128, "long_500k": 1}[shape]
        model_flops = 2 * na * tokens
    bound = max(terms.values())
    return {
        "arch": rec["arch"], "shape": shape, "n_devices": n,
        "compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll,
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_per_dev": flops,
        "useful_ratio": (model_flops / n) / flops if flops else 0.0,
        "step_lower_bound_s": bound,
        "roofline_fraction": t_compute / bound if bound else 0.0,
        "peak_gib_per_dev": rec["memory"]["peak_bytes"] / 2**30,
    }


def run(dryrun_dir: str = "results/dryrun", quiet: bool = False):
    rows = [analyze(r) for r in load_cells(dryrun_dir)]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    if not quiet:
        for r in rows:
            print(f"roofline,{r['arch']}|{r['shape']},"
                  f"{r['step_lower_bound_s']*1e6:.0f},"
                  f"dom={r['dominant']} comp={r['compute_s']*1e3:.2f}ms "
                  f"mem={r['memory_s']*1e3:.2f}ms "
                  f"coll={r['collective_s']*1e3:.2f}ms "
                  f"useful={r['useful_ratio']:.2f} "
                  f"rf={r['roofline_fraction']:.2f}")
    return rows


def write_csv(rows, path: str = "results/roofline.csv"):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    cols = ["arch", "shape", "n_devices", "compute_s", "memory_s",
            "collective_s", "dominant", "model_flops", "hlo_flops_per_dev",
            "useful_ratio", "roofline_fraction", "peak_gib_per_dev"]
    with open(path, "w") as f:
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r[c]) for c in cols) + "\n")


def pick_hillclimb(rows) -> dict[str, dict]:
    """The three §Perf cells: worst roofline fraction (train), most
    collective-bound, most representative of the paper's technique."""
    train = [r for r in rows if r["shape"].startswith("train")]
    worst = min(train, key=lambda r: r["roofline_fraction"])
    coll = max(rows, key=lambda r: r["collective_s"])
    # representative: decode — the cache-resident complex-instruction path
    decodes = [r for r in rows if "decode" in r["shape"]
               or r["shape"] == "long_500k"]
    rep = max(decodes, key=lambda r: r["memory_s"])
    return {"worst_fraction": worst, "most_collective": coll,
            "most_representative": rep}


def main(argv=None):
    import argparse
    import sys
    p = argparse.ArgumentParser(
        description="Roofline extraction from dry-run artifacts")
    p.add_argument("--dryrun-dir", default="results/dryrun",
                   help="directory of dry-run JSON artifacts")
    p.add_argument("--csv", default="results/roofline.csv",
                   help="CSV output path")
    p.add_argument("--out-json", default=None, metavar="PATH",
                   help="write rows + hillclimb picks as BENCH_roofline.json")
    args = p.parse_args(argv)
    rows = run(args.dryrun_dir, quiet=True)
    write_csv(rows, args.csv)
    for r in rows:
        print(f"roofline,{r['arch']}|{r['shape']},"
              f"{r['step_lower_bound_s']*1e6:.0f},"
              f"dom={r['dominant']} rf={r['roofline_fraction']:.2f} "
              f"useful={r['useful_ratio']:.2f}")
    picks = pick_hillclimb(rows)
    for k, r in picks.items():
        print(f"roofline_pick,{k},{r['arch']}|{r['shape']}")
    if args.out_json:
        sys.path.insert(0, __file__.rsplit("/", 1)[0])
        from common import bench_doc, write_bench_json
        peaks = chip_peaks(TARGET_DEVICE_KIND)
        doc = bench_doc(
            "roofline",
            config={"dryrun_dir": args.dryrun_dir,
                    "device_kind": TARGET_DEVICE_KIND,
                    "peak_flops": peaks.bf16_flops,
                    "hbm_bw": peaks.hbm_bw,
                    "ici_bw": peaks.ici_bw_per_link},
            rows=rows,
            summary={"picks": {k: f"{r['arch']}|{r['shape']}"
                               for k, r in picks.items()}})
        write_bench_json(args.out_json, doc)
        print(f"roofline,wrote,{args.out_json}")
    return rows


if __name__ == "__main__":
    main()
