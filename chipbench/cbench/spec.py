"""Finds a cell's files by the names in ``BENCHMARK.json``.

For a cell ``<config>.<traffic>``:

* ``chipbench/configs/<file>``: the configuration (``file`` of its entry);
* ``chipbench/traffic/<traffic>.json``: the traffic mix (``cbench.traffic``);
* ``chipbench/limits/<cell>.json``: the limits of its output check;
* ``chipbench/metrics/<metric>.py``: one reader per metric, a function
  ``read(ctx) -> float | None`` (``cbench.derive.Context``). A metric
  split by the end-to-end metric it moves (``device.idle_share.decode``,
  ``device.idle_share.prefill``) may share one reader named without the
  last part (``device.idle_share.py``);
* ``chipbench/equations/<config name>.py``, where it exists: the
  configuration's own float32 reference and counts, every function that
  ``Equations`` names. Without the file they are ``cbench.reference`` and
  ``cbench.counts`` (``default_equations``).

A cell reports the end-to-end metrics whose ``workloads`` name it (all,
where a metric has no such key); ``--trace 1`` reports the per-layer
metrics whose ``workloads`` name it or, without the key, whose ``moves``
is one of its end-to-end metrics.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Callable


@dataclasses.dataclass(frozen=True)
class Equations:
    """What the benchmark knows of a configuration's model. ``m`` is the
    configuration's ``model`` block; calls are ``(FLOPs, bytes)`` pairs.

    * ``logits_at(model, params, tokens, rows, *, quant=False)``: the
      float32 reference's logits at ``rows``, and with ``quant`` its int8
      control (``cbench.reference.logits_at``);
    * ``settled_at(model, params, tokens, rows)``: a bool per row, false
      where the reference's answer hangs on a discrete choice that the
      configuration's own precision could swap (a router's top-k within
      rounding); the check reads its gaps at the settled rows only
      (``cbench.reference.settled_at``: every row);
    * ``gemm_calls(m, rows, logit_rows)``: one program's GEMM kernel calls;
    * ``decode_attention_calls(m, lengths)``, ``flash_attention_calls(m, s)``:
      each layer's attention kernel call;
    * ``model_flops_decode(m, length)``, ``model_flops_prefill(m, s)``.
    """
    logits_at: Callable
    settled_at: Callable
    gemm_calls: Callable
    decode_attention_calls: Callable
    flash_attention_calls: Callable
    model_flops_decode: Callable
    model_flops_prefill: Callable


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
    equations: Equations


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
        end_to_end=e2e, per_layer=layer,
        equations=equations(root, w["config"]))


def equations(root: Path, config: str) -> Equations:
    """``chipbench/equations/<config>.py``, which has to define every
    function ``Equations`` names; without the file, ``default_equations``."""
    path = root / "chipbench" / "equations" / f"{config}.py"
    return _equations_file(path.resolve()) if path.is_file() else default_equations()


REFERENCE = ("logits_at", "settled_at")     # the rest are ``cbench.counts``'


@functools.lru_cache(maxsize=None)
def default_equations() -> Equations:
    """``cbench.reference``'s ``logits_at`` and ``settled_at`` and
    ``cbench.counts``' functions: the equations of ``attn`` and ``mla``
    layers with dense FFNs."""
    from cbench import counts, reference
    return Equations(**{f.name: getattr(reference if f.name in REFERENCE else counts,
                                        f.name)
                        for f in dataclasses.fields(Equations)})


@functools.lru_cache(maxsize=None)
def _equations_file(path: Path) -> Equations:
    """Loaded once a process, so that its functions keep their identity
    (``cbench.reference`` keys its compiled programs on them)."""
    mod = _module(path, f"chipbench_equations_{path.stem}")
    names = [f.name for f in dataclasses.fields(Equations)]
    missing = [n for n in names if not callable(getattr(mod, n, None))]
    if missing:
        raise AttributeError(f"{path} does not define {missing}")
    return Equations(*(getattr(mod, n) for n in names))


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
    return _module(reader_path(root, metric), f"chipbench_metric_{metric}").read


def _module(path: Path, name: str):
    s = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod
