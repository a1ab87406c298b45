from repro.kernels.expert_gemm.ops import *  # noqa: F401,F403
