"""GEMM kernel: sum over its calls in the traced steps of
max(FLOPs / peak, bytes / HBM bandwidth), over the device time of its
events, in percent. Calls from the cell's ``gemm_calls``."""
from cbench import derive
from cbench.programs import GEMM


def read(ctx):
    return derive.kernel_roofline(ctx, GEMM, derive.gemm_calls_of_step(ctx))
