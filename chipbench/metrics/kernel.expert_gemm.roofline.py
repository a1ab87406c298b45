"""Grouped expert GEMM kernel (``expert_gemm``, the routed experts of an
MoE layer) in the decode step: least time over device time, in percent,
over the decode programs of the traced steps.

Each decode is priced from what it routed: the session keeps, per output
token, the rows each held expert received in each MoE layer of the
program that produced it (``Request.expert_rows``; a decode step's one
array is shared by its tokens). In every MoE layer gate, up and down each
multiply those rows and read the weights of each held expert that
received any; the kernel never reads the others, so a count that assumed
every held expert would overstate the bytes wherever the routing leaves
some out. The device time is that of the kernel's events inside the
``jit_decode_step`` executions. Prefills are left out: XLA may stage a
prefill's expert weights in VMEM ahead of the call (the weight slices
then pay the HBM read), so their kernel time can beat the HBM bound that
prices them. None where the model has no routed experts, the program
keeps no such rows, or the trace holds no such kernel in a decode."""
import re

from cbench import counts, derive
from cbench.counts import BF16
from cbench.programs import DECODE

EXPERT_GEMM = r"expert_gemm(\.\d+)?"


def expert_gemm_calls(m, rows) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of one program's expert_gemm calls; ``rows``: per MoE
    layer, the rows each held expert received."""
    d, ff = m["d_model"], m["moe"]["d_expert"] or m["d_ff"]
    out = []
    for layer in rows:
        n = int(sum(int(r) for r in layer))
        touched = sum(1 for r in layer if r > 0)
        for k, w in ((d, ff), (d, ff), (ff, d)):
            out.append((2 * n * k * w, BF16 * (touched * k * w + n * (k + w))))
    return out


def decode_rows(ctx) -> list:
    """The rows arrays of the decode programs of the traced steps: every
    token but a request's first (its prefill's) comes from a decode."""
    ends = {st.t1 for st in derive.traced_steps(ctx)}
    progs = {}
    for r in ctx.reqs:
        rows = getattr(r.handle, "expert_rows", ())
        for t, a in list(zip(r.times, rows))[1:]:
            if t in ends:
                progs[id(a)] = a
    return list(progs.values())


def decode_seconds(trace, op_pattern: str) -> float:
    """Device seconds of the operations matching ``op_pattern`` whose
    midpoint lies in a decode execution, both inside the trace's window."""
    lo, hi = trace.window
    op_rx, mod_rx = re.compile(op_pattern), re.compile(DECODE)
    total = 0.0
    for dev, ops in trace.ops.items():
        mods = [(e.start, e.end) for e in trace.modules.get(dev, ())
                if mod_rx.fullmatch(e.name) and e.end > lo and e.start < hi]
        for e in ops:
            mid = (e.start + e.end) / 2
            if (op_rx.fullmatch(e.name) and e.end > lo and e.start < hi
                    and any(a <= mid <= b for a, b in mods)):
                total += e.end - e.start
    return total


def read(ctx):
    if not ctx.model.get("moe") or ctx.trace is None or ctx.peaks is None:
        return None
    calls = [c for rows in decode_rows(ctx)
             for c in expert_gemm_calls(ctx.model, rows)]
    spent = decode_seconds(ctx.trace, EXPERT_GEMM)
    if not calls or spent <= 0:
        return None
    return 100.0 * counts.least_seconds(calls, ctx.peaks) / spent
