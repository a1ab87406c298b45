"""Compile the main serving path for a described TPU v5e chip.

Nothing here runs on a chip. Each test lowers a program for one chip of a
``v5e:2x2`` topology that the TPU compiler installed with JAX describes, and
asserts the program holds a Mosaic kernel (``tpu_custom_call``): what the
chip's compiler refuses fails here, at no chip time. Shapes are stablelm-3b's
published widths (d_model 2560, 32 heads of 80, d_ff 6912, vocab 50304, bf16).

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
from repro.kernels import decode_attention, flash_attention, gemm
from repro.models.transformer import LM

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


def test_decode_step_compiles_at_batch_4(one_chip):
    """Two full-width layers of the served decode step on the pallas engine."""
    cfg = dataclasses.replace(get_config("stablelm-3b"), n_layers=2)
    model = LM(cfg, ArcaneEngine(backend="pallas", interpret=False))

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    text = _compiled_text(
        model.decode_step, on_chip(model.param_shapes()),
        _spec(one_chip, (4,), jnp.int32), _spec(one_chip, (4,), jnp.int32),
        on_chip(model.cache_shapes(4, MAX_LEN)))
    assert "tpu_custom_call" in text
