"""§V-C state-of-the-art comparison: peak throughput vs BLADE / Intel CNC.

Reproduces the paper's peak-GOPS comparison (scaled to 330 MHz-class
embedded-SRAM clocks as the paper does; ARCANE runs at 265 MHz there) and
adds this framework's TPU-target numbers: the roofline GOPS of the fused
conv-layer Pallas kernel on one v5e core, showing what the same "compute in
the cache" idea buys when the cache is VMEM and the VPU is the MXU.
"""
from __future__ import annotations

import argparse
import sys

from repro.peaks import chip_peaks

TPU_TARGET = "TPU v5 lite"

ARCANE_CLOCK = 265e6
PAPER = {
    # name: (peak GOPS, area mm², note)
    "BLADE (65nm, scaled)": (5.3, 0.58, "bit-line IMC, basic ops only"),
    "Intel CNC (Intel 4)": (25.0, 1.92, "MAC only"),
}


def arcane_peak_gops(lanes: int = 8) -> float:
    return lanes * 4 * 2 * ARCANE_CLOCK / 1e9


def run(quiet: bool = False):
    rows = []
    a_peak = arcane_peak_gops()
    # ARCANE LLC *subsystem* area (the paper's §V-C comparison unit: BLADE is
    # "3.18× smaller than ARCANE" with BLADE at 0.58 mm² → 1.85 mm²; the full
    # SoC including the host MCU is 3.34 mm², Table II)
    a_area = 3.18 * 0.58
    rows.append({"system": "ARCANE (this repro, 8-lane)", "gops": a_peak,
                 "area_mm2": a_area, "gops_per_mm2": a_peak / a_area})
    for name, (gops, area, note) in PAPER.items():
        rows.append({"system": name, "gops": gops, "area_mm2": area,
                     "gops_per_mm2": gops / area})
    # TPU target: one v5e chip's published int8 peak on the MXU
    tpu_int8 = chip_peaks(TPU_TARGET).int8_ops / 1e9
    rows.append({"system": "TPU v5e core (target, int8)", "gops": tpu_int8,
                 "area_mm2": float("nan"), "gops_per_mm2": float("nan")})
    if not quiet:
        for r in rows:
            print(f"sota,{r['system']},{r['gops']:.1f},GOPS "
                  f"({r['gops_per_mm2']:.1f} GOPS/mm2)" if r["area_mm2"] ==
                  r["area_mm2"] else f"sota,{r['system']},{r['gops']:.1f},GOPS")
    return rows


def validate(rows) -> dict:
    by = {r["system"]: r for r in rows}
    ours = by["ARCANE (this repro, 8-lane)"]
    blade = by["BLADE (65nm, scaled)"]
    cnc = by["Intel CNC (Intel 4)"]
    return {
        # paper: 17.0 GOPS peak, ~3.2x BLADE, CNC 1.47x faster than ARCANE
        "peak_close_to_17gops": abs(ours["gops"] - 17.0) < 1.0,
        "blade_ratio_3p2": abs(ours["gops"] / blade["gops"] - 3.2) < 0.3,
        "cnc_ratio_1p47": abs(cnc["gops"] / ours["gops"] - 1.47) < 0.15,
        "area_efficiency_close_to_blade":
            abs(ours["gops_per_mm2"] - blade["gops_per_mm2"])
            < 0.15 * blade["gops_per_mm2"],
    }


def main(argv=None):
    p = argparse.ArgumentParser(
        description="§V-C peak-throughput comparison (BLADE / Intel CNC)")
    p.add_argument("--out-json", default=None, metavar="PATH",
                   help="write rows + validation as BENCH_sota.json")
    args = p.parse_args(argv)
    rows = run(quiet=True)
    res = validate(rows)
    for k, v in res.items():
        print(f"sota_validate,{k},{v}")
    if args.out_json:
        sys.path.insert(0, __file__.rsplit("/", 1)[0])
        from common import bench_doc, write_bench_json
        doc = bench_doc("sota_throughput",
                        config={"arcane_clock_hz": ARCANE_CLOCK},
                        rows=rows, summary={"validate": res})
        write_bench_json(args.out_json, doc)
        print(f"sota,wrote,{args.out_json}")
    return rows


if __name__ == "__main__":
    main()
