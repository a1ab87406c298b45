"""Config registry, shape grid, and applicability rules (deliverable f)."""
import pytest

from repro.configs import (ARCHS, SHAPES, get_config, get_smoke_config, grid,
                           shape_applicable)


def test_ten_archs_registered():
    """The ten assigned architectures, and Moonlight-16B-A3B beside them."""
    assert len(ARCHS) == 11 and "moonlight-16b-a3b" in ARCHS


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_exact_published_geometry(arch):
    cfg = get_config(arch)
    expected = {
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "stablelm-3b": (32, 2560, 32, 32, 6912, 50304),
        "gemma2-9b": (42, 3584, 16, 8, 14336, 256000),
        "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expected


def test_moe_settings():
    g = get_config("granite-moe-1b-a400m").moe
    assert (g.n_experts, g.top_k) == (32, 8)
    l = get_config("llama4-scout-17b-a16e").moe
    assert (l.n_experts, l.top_k) == (16, 1)
    j = get_config("jamba-1.5-large-398b").moe
    assert (j.n_experts, j.top_k) == (16, 2)
    m = get_config("moonlight-16b-a3b")
    assert (m.moe.n_experts, m.moe.top_k, m.moe.d_expert, m.moe.n_shared,
            m.moe.router, m.moe.routed_scale) == (64, 6, 1408, 2, "sigmoid",
                                                  2.446)
    assert m.n_dense_layers == 1 and m.n_periods == 26
    assert m.mla.q_lora_rank is None and m.moe.held is None


def test_jamba_interleave_ratio():
    cfg = get_config("jamba-1.5-large-398b")
    kinds = [s.kind for s in cfg.pattern]
    assert kinds.count("attn") == 1 and kinds.count("mamba") == 7
    assert sum(s.moe for s in cfg.pattern) == 4      # MoE every other layer


def test_long_500k_applicability():
    long = SHAPES["long_500k"]
    runs = {a for a in ARCHS if shape_applicable(get_config(a), long)}
    assert runs == {"jamba-1.5-large-398b", "rwkv6-1.6b"}


def test_grid_cell_count():
    total = sum(len(grid(a)) for a in ARCHS)
    assert total == 35          # 11*3 + 2 long_500k


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_same_family(arch):
    full, smoke = get_config(arch), get_smoke_config(arch)
    assert smoke.family == full.family
    assert [s.kind for s in smoke.pattern] == [s.kind for s in full.pattern]
    assert (smoke.moe is None) == (full.moe is None)
    assert smoke.n_layers <= 8 and smoke.d_model <= 128
