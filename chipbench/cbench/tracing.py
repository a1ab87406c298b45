"""Profiler trace capture and its reduction to device times.

A traced run records a few seconds of its window with ``jax.profiler``;
the harness marks the traced steps with a host span ``chipbench.traced``
and each phase of its loop with ``chipbench.<phase>`` spans, which the
profiler writes on the same clock as the device's events. The reduction
reads, per device plane (``/device:TPU:<n>``):

* ``XLA Ops``: every operation the device ran, named by its HLO
  instruction (kernels by the jitted function that holds them: ``gemm.12``,
  ``decode_attention.3``); a ``while`` (the scan over layers) spans the
  operations of its body, so it counts toward busy time but not among the
  operations;
* ``XLA Modules``: every execution of a compiled program
  (``jit_decode_step(...)``),

and from the host plane the harness's spans. Everything is clipped to the
``chipbench.traced`` span.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

SPAN = "chipbench."
WINDOW_SPAN = "chipbench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
CONTAINER = re.compile(r"(while|conditional|call)(\.\d+)?")


def op_name(text: str) -> str:
    """``%gemm.12 = bf16[...] custom-call(...)`` -> ``gemm.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Ev:
    name: str
    start: float          # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]
    ops: dict[int, list[Ev]]          # device id -> operations
    modules: dict[int, list[Ev]]      # device id -> program executions
    host: list[Ev]                    # the harness's spans

    # ----------------------------------------------------------- queries
    def _clip(self, evs: list[Ev]) -> list[Ev]:
        lo, hi = self.window
        return [e for e in evs if e.end > lo and e.start < hi]

    def _leaf_ops(self, dev: int) -> list[Ev]:
        return [e for e in self._clip(self.ops[dev])
                if not CONTAINER.fullmatch(e.name)]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, dev: int) -> list[tuple[float, float]]:
        """Union of the device's operation intervals, clipped to the window."""
        lo, hi = self.window
        out: list[list[float]] = []
        for e in sorted(self._clip(self.ops[dev]), key=lambda e: e.start):
            s, t = max(e.start, lo), min(e.end, hi)
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t)
            else:
                out.append([s, t])
        return [(s, t) for s, t in out]

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices in the trace."""
        devs = sorted(self.ops)
        return sum(sum(t - s for s, t in self.busy_intervals(d))
                   for d in devs) / len(devs)

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of operations whose name matches ``pattern``
        (``re.fullmatch``), summed over devices."""
        rx = re.compile(pattern)
        return sum(e.end - e.start for d in self.ops
                   for e in self._leaf_ops(d) if rx.fullmatch(e.name))

    def module_runs(self, pattern: str) -> list[float]:
        """Durations of the program executions whose name matches."""
        rx = re.compile(pattern)
        return [e.end - e.start for d in self.modules
                for e in self._clip(self.modules[d]) if rx.fullmatch(e.name)]

    def top_ops(self, n: int = 10) -> list[list]:
        """Device seconds by operation, ``.N`` suffixes merged."""
        acc: collections.Counter = collections.Counter()
        for d in self.ops:
            for e in self._leaf_ops(d):
                acc[re.sub(r"\.\d+$", "", e.name)] += e.end - e.start
        return [[k, v] for k, v in acc.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle device seconds by what the host was doing meanwhile: the
        innermost harness span at each gap's midpoint (``none`` outside
        every span), summed over gaps and averaged over devices."""
        lo, hi = self.window
        spans = [e for e in self.host if e.name != WINDOW_SPAN]
        acc: collections.Counter = collections.Counter()
        for d in self.ops:
            edge = lo
            for s, t in self.busy_intervals(d) + [(hi, hi)]:
                if s > edge:
                    mid = (edge + s) / 2
                    inside = [e for e in spans if e.start <= mid < e.end]
                    name = (min(inside, key=lambda e: e.end - e.start).name
                            if inside else "none")
                    acc[name] += (s - edge) / len(self.ops)
                edge = max(edge, t)
        return [[k, v] for k, v in acc.most_common(n)]


def load(path: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {path}")
    return from_profile(ProfileData.from_file(files[-1]))


def from_profile(pd) -> Trace:
    ops: dict[int, list[Ev]] = {}
    modules: dict[int, list[Ev]] = {}
    host: list[Ev] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dest = (ops if line.name == OPS_LINE else modules)
                name = op_name if line.name == OPS_LINE else str
                dest.setdefault(int(m.group(1)), []).extend(
                    Ev(name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events)
            elif plane.name == "/host:CPU":
                host.extend(Ev(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events if e.name.startswith(SPAN))
    win = [e for e in host if e.name == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(win)}")
    if not ops:
        raise ValueError("no device operations in the trace")
    for d in ops:
        modules.setdefault(d, [])
    return Trace(window=(win[0].start, win[0].end), ops=ops,
                 modules=modules, host=host)
