"""Device milliseconds of the prefill programs (``jit_prefill``) per 1,000
prompt tokens admitted in the traced steps."""
from cbench import derive
from cbench.programs import PREFILL


def read(ctx):
    if ctx.trace is None:
        return None
    toks = sum(sum(s.prefill_lens) for s in derive.traced_steps(ctx))
    runs = ctx.trace.module_runs(PREFILL)
    return 1e6 * sum(runs) / toks if toks and runs else None
