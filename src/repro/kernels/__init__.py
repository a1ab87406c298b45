"""Pallas TPU kernel suite — the xmnmc micro-programs + attention kernels.

Each kernel package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jitted
wrapper), ref.py (pure-jnp oracle). Validated in interpret mode on CPU.
"""
from repro.kernels.gemm.ops import gemm
from repro.kernels.convlayer.ops import conv_layer
from repro.kernels.maxpool.ops import maxpool
from repro.kernels.leakyrelu.ops import leakyrelu
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.kv_write.ops import kv_write
from repro.kernels.expert_gemm.ops import expert_gemm

__all__ = ["gemm", "conv_layer", "maxpool", "leakyrelu", "flash_attention",
           "decode_attention", "kv_write", "expert_gemm"]
