"""Compile the main serving path for a described TPU v5e chip.

Nothing here runs on a chip. Each test lowers a program for one chip of a
``v5e:2x2`` topology that the TPU compiler installed with JAX describes, and
asserts the program holds a Mosaic kernel (``tpu_custom_call``): what the
chip's compiler refuses fails here, at no chip time. Shapes are stablelm-3b's
published widths (d_model 2560, 32 heads of 80, d_ff 6912, vocab 50304, bf16),
and Moonlight-16B-A3B's for the routed experts (d_model 2048, experts of
1408, 8 held).

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and the test workers
must all collect the same tests. Keep every such compile in this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.engine import ArcaneEngine
from repro.kernels import decode_attention, expert_gemm, flash_attention, gemm
from repro.models.transformer import LM
from repro.serving.engine import select_tokens
from repro.train.step import make_serve_steps

D, HEADS, HEAD_DIM, D_FF, VOCAB = 2560, 32, 80, 6912, 50304
MAX_LEN = 2048
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent cache off (a
    compile for a described chip is written but cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in TMPDIR
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            jax.config.update("jax_enable_compilation_cache", was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,n,out_dtype", [
    (4, D, HEADS * HEAD_DIM, BF16),      # decode QKV projection
    (512, D, D_FF, BF16),                # prefill FFN up-projection
    (4, D, VOCAB, jnp.float32),          # decode unembedding
], ids=["decode_qkv", "prefill_ffn", "unembed"])
def test_gemm_compiles(one_chip, m, k, n, out_dtype):
    text = _compiled_text(
        lambda a, b: gemm(a, b, out_dtype=out_dtype, interpret=False),
        _spec(one_chip, (m, k)), _spec(one_chip, (k, n)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("seq", [20, 1024])
def test_flash_attention_compiles(one_chip, seq):
    qkv = _spec(one_chip, (1, HEADS, seq, HEAD_DIM))
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [1, 4])
def test_decode_attention_compiles(one_chip, batch):
    kv = _spec(one_chip, (batch, HEADS, MAX_LEN, HEAD_DIM))
    text = _compiled_text(
        lambda q, k, v, n: decode_attention(q, k, v, n, block_k=256,
                                            interpret=False),
        _spec(one_chip, (batch, HEADS, HEAD_DIM)), kv, kv,
        _spec(one_chip, (batch,), jnp.int32))
    assert "tpu_custom_call" in text


def _decode_step_text(sharding, max_len: int) -> str:
    """Two full-width layers of the served decode step on the pallas engine,
    at batch 4, compiled."""
    cfg = dataclasses.replace(get_config("stablelm-3b"), n_layers=2)
    model = LM(cfg, ArcaneEngine(backend="pallas", interpret=False))

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), tree)

    return _compiled_text(
        model.decode_step, on_chip(model.param_shapes()),
        _spec(sharding, (4,), jnp.int32), _spec(sharding, (4,), jnp.int32),
        on_chip(model.cache_shapes(4, max_len)))


def test_decode_step_compiles_at_batch_4(one_chip):
    assert "tpu_custom_call" in _decode_step_text(one_chip, MAX_LEN)


def test_decode_step_compiles_at_unaligned_max_len(one_chip):
    """A max_len that 128 does not divide: the K/V write and the decode
    kernel still move 128-lane blocks (the last one runs past the cache),
    never a whole layer, so the step fits the chip's scoped VMEM."""
    assert "tpu_custom_call" in _decode_step_text(one_chip, 1000)


@pytest.mark.parametrize("rows,k,n,block_m", [
    (512, 2048, 1408, 16),     # decode at 64 slots: gate / up
    (512, 1408, 2048, 16),     # decode: down
    (3456, 2048, 1408, 48),    # prefill of 512 tokens
], ids=["decode_up", "decode_down", "prefill_up"])
def test_expert_gemm_compiles(one_chip, rows, k, n, block_m):
    text = _compiled_text(
        lambda x, w, s: expert_gemm(x, w, s, block_m=block_m, interpret=False),
        _spec(one_chip, (rows, k)), _spec(one_chip, (8, k, n)),
        _spec(one_chip, (8,), jnp.int32))
    assert "tpu_custom_call" in text and "%expert_gemm" in text


def test_moonlight_decode_step_compiles(one_chip):
    """The dense first layer and one MoE layer of Moonlight-16B-A3B at its
    published widths, 8 experts held, at 64 slots of 1024, as the session
    runs it (with the rows per held expert): the grouped expert kernel
    keeps its name beside the GEMM and decode kernels."""
    full = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(full, n_layers=2, moe=dataclasses.replace(
        full.moe, held=(0, 8)))
    model = LM(cfg, ArcaneEngine(backend="pallas", interpret=False))

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    text = _compiled_text(
        make_serve_steps(model, expert_rows=True)[1],
        on_chip(model.param_shapes()),
        _spec(one_chip, (64,), jnp.int32), _spec(one_chip, (64,), jnp.int32),
        on_chip(model.cache_shapes(64, 1024)))
    for kernel in ("%expert_gemm", "%gemm", "%decode_attention"):
        assert kernel in text


@pytest.mark.parametrize("batch,vocab", [(64, 163840), (12, VOCAB)],
                         ids=["moonlight_64", "stablelm_12"])
def test_select_tokens_compiles(one_chip, batch, vocab):
    """The serving programs' token selection at a cell's slots and
    vocabulary: the random draw sits behind a conditional, and neither
    branch keeps a (B, V) array of its own."""
    compiled = jax.jit(
        lambda lg, t, key, n: select_tokens(lg, t, jax.random.fold_in(key, n))
    ).lower(_spec(one_chip, (batch, vocab), jnp.float32),
            _spec(one_chip, (batch,), jnp.float32),
            jax.eval_shape(lambda: jax.random.key(0)),
            _spec(one_chip, (), jnp.uint32)).compile()
    assert "conditional" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < batch * vocab * 4
