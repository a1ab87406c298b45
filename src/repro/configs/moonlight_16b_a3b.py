"""moonlight-16b-a3b — 27L d2048 16H MLA (direct q, kv_lora 512, nope/rope/v
128/64/128), layer 0 dense (d_ff 11264), layers 1-26 MoE: 64 routed experts
of 1408, top 6, sigmoid scores with a selection-only bias, gates renormalised
and scaled by 2.446, 2 shared experts; vocab 163840, untied
[hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3].

``config()`` holds every routed expert; a deployment that spreads them over
chips gives each its range (``MoEConfig.held``).
"""
from repro.configs.base import LayerSpec, MLAConfig, ModelConfig, MoEConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=27, n_dense_layers=1, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=11264, vocab=163840, head_dim=192,
        pattern=(LayerSpec(kind="mla", moe=True),),
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                      router="sigmoid", routed_scale=2.446),
        rope_theta=50000.0, tie_embeddings=False, max_seq_len=8192,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="moonlight-smoke", family="moe",
        n_layers=3, n_dense_layers=1, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=96, vocab=256, head_dim=24,
        pattern=(LayerSpec(kind="mla", moe=True),),
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=16,
                      qk_nope_head_dim=16, qk_rope_head_dim=8,
                      v_head_dim=16),
        moe=MoEConfig(n_experts=8, top_k=3, d_expert=32, n_shared=2,
                      router="sigmoid", routed_scale=2.446, held=(2, 6)),
        rope_theta=50000.0, tie_embeddings=False, max_seq_len=128,
    )
