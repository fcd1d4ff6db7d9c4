"""Compile the main-path kernels and steps for a described TPU v5e chip.

No chip is attached: the TPU compiler that ships with jaxlib compiles for a
topology that is only described, and refuses what the chip would refuse
(block tiling, VMEM, HBM).  Nothing runs, so these tests say nothing about
results or times; they guard that Mosaic accepts the kernels at the widths
the smoke test serves (``llama3.2-1b``: d_model 2048, d_ff 8192, 32 heads
of 64) and with the committed ``tuned/tpu-v5e.json`` blocks.

The topology is described only inside the ``topo`` fixture: the process
that describes it loads the TPU library and keeps its lock, so doing it at
import would break every other test worker.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.catalog import get_config
from repro.core import GLOBAL_REGISTRY, execution_context
from repro.core.attention_api import flash_tile_lookup
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.gemm import gemm_pallas
from repro.models import build_model

HW = "tpu-v5e"
BF16 = jnp.bfloat16
D_MODEL, D_FF, HEADS, HEAD_DIM = 2048, 8192, 32, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """Compiles for a described chip cannot be read back from JAX's
    persistent cache; keep it off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    return lowered, lowered.compile()


@pytest.mark.parametrize("m,k,n,bias,activation", [
    (4096, D_MODEL, D_FF, False, None),        # w_up, prefill of 8 x 512
    (4096, D_MODEL, D_FF, True, "silu"),       # fused bias + SiLU epilogue
    (4096, D_FF, D_MODEL, True, None),         # w_down
])
def test_gemm_compiles(one_chip, no_compile_cache, m, k, n, bias,
                       activation):
    cfg = GLOBAL_REGISTRY.lookup(HW, BF16, m, k, n).config
    shapes = [jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)
              for s in ((m, k), (k, n)) + (((n,),) if bias else ())]

    def fn(a, b, *bias_):
        return gemm_pallas(a, b, bias=bias_[0] if bias_ else None,
                           activation=activation, bm=cfg.bm, bk=cfg.bk,
                           bn=cfg.bn)

    _, compiled = _compile(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("sq", [512, 600])   # 600: not a multiple of bq
def test_flash_compiles_ragged(one_chip, no_compile_cache, sq):
    """Two rows of 32 heads with per-row kv_start (left-padded prompts)."""
    bh = 2 * HEADS
    cfg = flash_tile_lookup(HW, BF16, sq, sq, HEAD_DIM).config
    assert (sq % cfg.bq != 0) == (sq == 600), cfg
    qkv = jax.ShapeDtypeStruct((bh, sq, HEAD_DIM), BF16, sharding=one_chip)
    kv_start = jax.ShapeDtypeStruct((bh,), jnp.int32, sharding=one_chip)

    def fn(q, k, v, kv_start):
        return flash_attention_bhsd(q, k, v, causal=True, bq=cfg.bq,
                                    bk=cfg.bk, kv_start=kv_start)

    _, compiled = _compile(fn, qkv, qkv, qkv, kv_start)
    assert "tpu_custom_call" in compiled.as_text()


def _llama(**overrides):
    return build_model(dataclasses.replace(get_config("llama3.2-1b"),
                                           **overrides))


def test_full_width_prefill_step_compiles(one_chip, no_compile_cache):
    """The serving prefill of full-width llama3.2-1b (16 layers), 8 ragged
    rows of 512 tokens, with every matmul and the attention as Mosaic
    kernels."""
    model = _llama(attention_impl="flash")
    b, s = 8, 512
    params = _shapes(model.abstract(), one_chip)
    cache = _shapes(jax.eval_shape(lambda: model.init_cache(b, s)), one_chip)
    batch = _shapes({"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                     "kv_start": jax.ShapeDtypeStruct((b,), jnp.int32)},
                    one_chip)
    with execution_context(backend="pallas-tpu", hardware=HW):
        lowered, compiled = _compile(model.prefill, params, batch, cache)
    text = lowered.as_text()
    assert 'kernel_name = "_gemm_kernel"' in text
    assert 'kernel_name = "_flash_kernel"' in text
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_step_compiles(one_chip, no_compile_cache):
    """One decode step of full-width llama3.2-1b over 8 rows of a 512-token
    cache: every projection and the unembedding as Mosaic kernels."""
    model = _llama(attention_impl="flash")
    b = 8
    params = _shapes(model.abstract(), one_chip)
    cache = _shapes(jax.eval_shape(lambda: model.init_cache(b, 512)),
                    one_chip)
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=one_chip)
    with execution_context(backend="pallas-tpu", hardware=HW):
        lowered, compiled = _compile(model.decode_step, params, i32((b, 1)),
                                     cache, i32(()), i32((b,)))
    assert 'kernel_name = "_gemm_kernel"' in lowered.as_text()
    assert "tpu_custom_call" in compiled.as_text()


def test_train_step_compiles(one_chip, no_compile_cache):
    """One optimizer step through the Pallas GEMM's custom VJP, with the
    config's chunked attention (the flash kernel has no backward).  Full
    widths, but 2 layers instead of 16: the f32 AdamW state of all 16
    would not fit one chip's 16 GB next to the bf16 weights."""
    from repro.optim import AdamW
    from repro.train import abstract_train_state
    from repro.train.trainer import make_train_step
    model = _llama(num_layers=2)
    opt = AdamW(learning_rate=1e-4)
    state = _shapes(abstract_train_state(model, opt), one_chip)
    tokens = jax.ShapeDtypeStruct((2, 512), jnp.int32, sharding=one_chip)
    batch = {"tokens": tokens, "labels": tokens}
    with execution_context(backend="pallas-tpu", hardware=HW):
        lowered, compiled = _compile(make_train_step(model, opt), state,
                                     batch)
    assert 'kernel_name = "_gemm_kernel"' in lowered.as_text()
    assert "tpu_custom_call" in compiled.as_text()
