from repro.kernels.kv_write.ops import *  # noqa: F401,F403
