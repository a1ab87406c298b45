"""Model FLOPs of the prompts prefilled in the traced steps over the
prefill programs' device time times the chip's bf16 peak, in percent."""
from cbench import derive
from cbench.programs import PREFILL


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    lens = [s for st in derive.traced_steps(ctx) for s in st.prefill_lens]
    spent = sum(ctx.trace.module_runs(PREFILL))
    if not lens or spent <= 0:
        return None
    flops = sum(ctx.equations.model_flops_prefill(ctx.model, s) for s in lens)
    return 100.0 * flops / (spent * ctx.peaks.bf16_flops)
