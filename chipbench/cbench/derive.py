"""Quantities that several metric readers share, from the loop's records."""
from __future__ import annotations

import dataclasses
from typing import Optional

from cbench import counts
from cbench.peaks import ChipPeaks
from cbench.spec import Equations, default_equations
from cbench.tracing import Trace


@dataclasses.dataclass
class Context:
    """What a metric reader (``chipbench/metrics/<name>.py``) reads."""
    model: dict                   # the configuration's ``model`` block
    mix: dict                     # the traffic mix
    reqs: list                    # loop.Req, every request sent
    steps: list                   # loop.Step, every step taken
    window: tuple                 # (t0, t1) on the host clock
    setup_s: float
    compiles_in_window: int
    peaks: Optional[ChipPeaks]    # None off a known chip
    trace: Optional[Trace] = None
    equations: Equations = dataclasses.field(      # the cell's (``spec.Cell``)
        default_factory=default_equations)


def window_steps(ctx) -> list:
    t0, t1 = ctx.window
    return [s for s in ctx.steps if s.t0 >= t0 and s.t1 <= t1]


def traced_steps(ctx) -> list:
    return [s for s in ctx.steps if s.traced]


def due_in_window(ctx) -> list:
    t0, t1 = ctx.window
    return [r for r in ctx.reqs if t0 <= r.t_due <= t1]


def ttfts_s(ctx) -> list[float]:
    """Due (open loop) or sent (closed loop) to first token, for every
    request due in the window; one still waiting enters with its wait so
    far, so a stall raises the tail."""
    t1 = ctx.window[1]
    return [(r.t_first if r.t_first is not None and r.t_first <= t1 else t1)
            - r.t_due for r in due_in_window(ctx)]


def token_gaps_s(ctx) -> list[float]:
    """Every gap between consecutive output tokens of a request, both
    tokens inside the window."""
    t0, t1 = ctx.window
    out = []
    for r in ctx.reqs:
        ts = [t for t in r.times if t0 <= t <= t1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(ctx) -> int:
    t0, t1 = ctx.window
    return sum(1 for r in ctx.reqs for t in r.times if t0 < t <= t1)


def step_model_flops(ctx, step) -> int:
    eq, m = ctx.equations, ctx.model
    return (sum(eq.model_flops_prefill(m, s) for s in step.prefill_lens)
            + sum(eq.model_flops_decode(m, n) for n in step.decode_lens))


def kernel_roofline(ctx, op_pattern: str, calls_of_step) -> Optional[float]:
    """Sum of least times over sum of device time of the kernel's events,
    over the traced steps, in percent; None where the trace holds none."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    spent = ctx.trace.op_seconds(op_pattern)
    calls = [c for st in traced_steps(ctx) for c in calls_of_step(st)]
    if spent <= 0 or not calls:
        return None
    return 100.0 * counts.least_seconds(calls, ctx.peaks) / spent


def gemm_calls_of_step(ctx):
    """GEMM calls of a step: the decode at the session's full batch (it
    decodes every slot) and a batch-1 prefill per admitted prompt."""
    m, b, gemm_calls = ctx.model, ctx.mix["max_slots"], ctx.equations.gemm_calls

    def calls(st):
        out = [c for s in st.prefill_lens for c in gemm_calls(m, s, 1)]
        if st.decode_lens:
            out += gemm_calls(m, b, b)
        return out
    return calls


def idle_share(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
