"""Device milliseconds per execution of the decode program
(``jit_decode_step``), from the trace's program executions."""
from cbench.programs import DECODE


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.module_runs(DECODE)
    return 1e3 * sum(runs) / len(runs) if runs else None
