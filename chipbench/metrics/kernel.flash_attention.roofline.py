"""Flash-attention kernel (prefill): least time over device time, in
percent, over the prompts admitted in the traced steps."""
from cbench import derive
from cbench.programs import FLASH_ATTENTION


def read(ctx):
    return derive.kernel_roofline(
        ctx, FLASH_ATTENTION,
        lambda st: [c for s in st.prefill_lens
                    for c in ctx.equations.flash_attention_calls(ctx.model, s)])
