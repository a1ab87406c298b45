"""Names under which the program's work shows in a TPU profiler trace.

Kernels appear in the ``XLA Ops`` line as the custom call of the jitted
wrapper that holds them (``gemm.7``, ``decode_attention.2``,
``flash_attention.1``), and compiled programs in the ``XLA Modules`` line
as ``jit_<function>(<id>)``. Patterns are ``re.fullmatch``-ed.
"""
GEMM = r"gemm(\.\d+)?"
DECODE_ATTENTION = r"decode_attention(\.\d+)?"
FLASH_ATTENTION = r"flash_attention(\.\d+)?"
DECODE = r"jit_decode_step(\(.*\))?"
PREFILL = r"jit_prefill(\(.*\))?"
