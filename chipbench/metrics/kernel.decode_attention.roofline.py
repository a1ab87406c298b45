"""Decode-attention kernel: least time over device time, in percent, with
the cache bytes of each live slot's own length (never ``max_len``)."""
from cbench import derive
from cbench.programs import DECODE_ATTENTION


def read(ctx):
    return derive.kernel_roofline(
        ctx, DECODE_ATTENTION,
        lambda st: ctx.equations.decode_attention_calls(ctx.model, st.decode_lens)
        if st.decode_lens else [])
