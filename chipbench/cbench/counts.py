"""Operations and bytes the served model asks for, from its published widths.

One fixed formula per layer kind, over the logical (unpadded) shapes the
model asks each kernel for. Nothing here reads HLO or the program's own
FLOP log. ``m`` is the ``model`` block of a configuration file. Matrix
operands are bfloat16 (2 bytes); the unembedding writes float32 logits.

Decode attention counts the cache rows up to each live slot's length,
never the allocated ``max_len``: a kernel that reads only live pages then
comes nearer its roofline, and no count can push a share past 100%.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def _hd(m) -> int:
    return m["head_dim"] or m["d_model"] // m["n_heads"]


def _kinds(m) -> list[str]:
    per = [p["kind"] for p in m["pattern"]]
    return per * (m["n_layers"] // len(per))


def gemm_cost(mm: int, k: int, n: int, out_bytes: int = BF16) -> tuple[int, int]:
    """(FLOPs, bytes) of one (mm, k) @ (k, n) GEMM kernel call."""
    return 2 * mm * k * n, BF16 * (mm * k + k * n) + out_bytes * mm * n


def layer_gemms(m, kind: str, rows: int) -> list[tuple[int, int, int]]:
    """The (M, K, N) of the GEMM kernel calls of one layer over ``rows``
    tokens. The latent up-projections of ``mla`` are XLA einsums, not
    GEMM kernel calls, and are counted in ``model_flops_*`` only."""
    d, ff = m["d_model"], m["d_ff"]
    if kind == "attn":
        hd = _hd(m)
        out = [(rows, d, m["n_heads"] * hd), (rows, d, m["n_kv_heads"] * hd),
               (rows, d, m["n_kv_heads"] * hd), (rows, m["n_heads"] * hd, d)]
    elif kind == "mla":
        a = m["mla"]
        qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
        out = [(rows, d, a["q_lora_rank"]),
               (rows, a["q_lora_rank"], m["n_heads"] * qk),
               (rows, d, a["kv_lora_rank"] + a["qk_rope_head_dim"]),
               (rows, m["n_heads"] * a["v_head_dim"], d)]
    else:
        raise ValueError(f"no counts for layer kind {kind!r}")
    return out + [(rows, d, ff), (rows, d, ff), (rows, ff, d)]


def gemm_calls(m, rows: int, logit_rows: int) -> list[tuple[int, int]]:
    """(FLOPs, bytes) of every GEMM kernel call of one program: all layers
    over ``rows`` tokens, then the unembedding over ``logit_rows``."""
    calls = [gemm_cost(*c) for kind in _kinds(m) for c in layer_gemms(m, kind, rows)]
    calls.append(gemm_cost(logit_rows, m["d_model"], m["vocab"], F32))
    return calls


def decode_attention_calls(m, lengths: list[int]) -> list[tuple[int, int]]:
    """(FLOPs, bytes) of each layer's decode-attention kernel call over
    slots whose caches hold ``lengths`` rows (live rows only)."""
    b, live = len(lengths), sum(lengths)
    out = []
    for kind in _kinds(m):
        if kind == "attn":
            hd, h, hkv = _hd(m), m["n_heads"], m["n_kv_heads"]
            flops = 4 * live * h * hd
            byts = BF16 * (2 * live * hkv * hd + 2 * b * h * hd)
        else:
            a = m["mla"]
            r, rope, h = a["kv_lora_rank"], a["qk_rope_head_dim"], m["n_heads"]
            flops = 2 * h * live * (r + rope) + 2 * h * live * r
            byts = BF16 * (live * (r + rope) + live * r + b * h * (r + rope) + b * h * r)
        out.append((flops, byts))
    return out


def flash_attention_calls(m, s: int) -> list[tuple[int, int]]:
    """(FLOPs, bytes) of each layer's causal flash-attention call over a
    prompt of ``s`` tokens (batch 1)."""
    out = []
    for kind in _kinds(m):
        h = m["n_heads"]
        if kind == "attn":
            dq = dv = _hd(m)
            hkv = m["n_kv_heads"]
            byts = BF16 * s * (2 * h * dq + 2 * hkv * dq)
        else:
            a = m["mla"]
            dq, dv = a["qk_nope_head_dim"] + a["qk_rope_head_dim"], a["v_head_dim"]
            byts = BF16 * s * h * (2 * dq + 2 * dv)
        out.append((h * s * (s + 1) * (dq + dv), byts))
    return out


def _proj_flops(m, kind: str) -> int:
    """Matmul FLOPs per token of one layer's projections and FFN."""
    return sum(2 * k * n for _, k, n in layer_gemms(m, kind, 1))


def model_flops_decode(m, length: int) -> int:
    """Model FLOPs of one decode token attending over ``length`` rows."""
    total = 2 * m["d_model"] * m["vocab"]
    for kind in _kinds(m):
        total += _proj_flops(m, kind)
        if kind == "attn":
            total += 4 * length * m["n_heads"] * _hd(m)
        else:
            a = m["mla"]
            r, rope, h = a["kv_lora_rank"], a["qk_rope_head_dim"], m["n_heads"]
            total += 2 * h * r * (a["qk_nope_head_dim"] + a["v_head_dim"])
            total += 2 * h * length * (r + rope) + 2 * h * length * r
    return total


def model_flops_prefill(m, s: int) -> int:
    """Model FLOPs of prefilling ``s`` prompt tokens (logits of the last)."""
    total = 2 * m["d_model"] * m["vocab"]
    attn = flash_attention_calls(m, s)
    for kind, (fl, _) in zip(_kinds(m), attn):
        total += s * _proj_flops(m, kind) + fl
        if kind == "mla":
            a = m["mla"]
            total += 2 * s * a["kv_lora_rank"] * m["n_heads"] * (
                a["qk_nope_head_dim"] + a["v_head_dim"])
    return total


def least_seconds(calls, peaks) -> float:
    """Sum over calls of max(FLOPs / peak FLOP/s, bytes / HBM bandwidth)."""
    return sum(max(f / peaks.bf16_flops, b / peaks.hbm_bw) for f, b in calls)
