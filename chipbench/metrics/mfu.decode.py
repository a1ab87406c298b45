"""Model FLOPs of every token processed in the traced steps (prompts
prefilled and tokens decoded) over the traced window's seconds times the
chip's bf16 peak, in percent. The traced window leaves out the profiler's
own start and stop."""
from cbench import derive


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    flops = sum(derive.step_model_flops(ctx, s)
                for s in derive.traced_steps(ctx))
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks.bf16_flops)
