"""Finds a cell's files by the names in ``BENCHMARK.json``.

For a cell ``<config>.<traffic>``:

* ``chipbench/configs/<file>``: the configuration (``file`` of its entry);
* ``chipbench/traffic/<traffic>.json``: the traffic mix (``cbench.traffic``);
* ``chipbench/limits/<cell>.json``: the limits of its output check;
* ``chipbench/metrics/<metric>.py``: one reader per metric, a function
  ``read(ctx) -> float | None`` (``cbench.derive.Context``). A metric
  split by the end-to-end metric it moves (``device.idle_share.decode``,
  ``device.idle_share.prefill``) may share one reader named without the
  last part (``device.idle_share.py``).

A cell reports the end-to-end metrics whose ``workloads`` name it (all,
where a metric has no such key); ``--trace 1`` reports the per-layer
metrics whose ``workloads`` name it or, without the key, whose ``moves``
is one of its end-to-end metrics.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    base = root / "chipbench"
    return Cell(
        root=root, name=workload, chips=w["chips"],
        config=json.loads((root / cfg_entry["file"]).read_text()),
        mix=json.loads((base / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((base / "limits" / f"{workload}.json").read_text()),
        end_to_end=e2e, per_layer=layer)


def reader_path(root: Path, metric: str) -> Path:
    """``chipbench/metrics/<metric>.py``, else the file of the name with its
    last dotted part dropped, and so on."""
    base, name = root / "chipbench" / "metrics", metric
    while not (base / f"{name}.py").is_file():
        if "." not in name:
            raise FileNotFoundError(f"no reader for metric {metric!r} in {base}")
        name = name.rsplit(".", 1)[0]
    return base / f"{name}.py"


def reader(root: Path, metric: str):
    """The ``read`` function of the metric's reader file."""
    path = reader_path(root, metric)
    s = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read
