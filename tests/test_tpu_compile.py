"""Compile the served path's Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel's math on the CPU but never applies the TPU
compiler's tiling and VMEM rules. These tests lower and compile the
flash-decode, fused-query and Expected-Attention kernels for one chip of a
described (not attached) `v5e:2x2` topology, at the planted models' widths,
at Llama-3.1-8B's published widths (d_head 128, 8 KV heads, 4 query heads
per KV head) and at Yi-34B's (d_head 128, 8 KV heads, 7 query heads per
KV head: query rows not a power of two), with float32, bf16 and int8
caches. A refused block
shape or an over-budget kernel fails here, in about two seconds a case,
instead of on the chip. Nothing runs, so no result is checked.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU library, and every worker collects the same
tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.llava_next_34b import CONFIG as YI_34B
from repro.configs.stretto_llama_8b import CONFIG as LLAMA_8B
from repro.data.synthetic import planted_config
from repro.kernels.decode_attention import (decode_attention,
                                            decode_query_attention)
from repro.kernels.expected_attention import expected_attention_scores

B, S, LQ = 8, 512, 6

WIDTHS = {name: (c.n_kv_heads, c.n_heads // c.n_kv_heads, c.d_head)
          for name, c in (("planted-sm", planted_config("sm")),
                          ("planted-lg", planted_config("lg")),
                          ("llama-8b", LLAMA_8B),
                          ("yi-34b", YI_34B))}   # LLaVA-NeXT-34B's LM


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory placed on one described chip. A compile
    against a described device cannot be read back from the persistent
    compilation cache, so the cache is off for this module."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _cache_args(shape, KV, dk, dtype):
    kv = [shape((B, S, KV, dk), dtype)] * 2
    scales = ([shape((B, S, KV), jnp.float32)] * 2
              if dtype == jnp.int8 else [])
    return kv, scales


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("fused", [False, True], ids=["decode", "query"])
def test_attention_kernel_compiles(shape, fused, width, dtype):
    KV, G, dk = WIDTHS[width]
    q_dtype = jnp.float32 if dtype == jnp.int8 else dtype
    q_shape = (B, LQ, KV, G, dk) if fused else (B, KV, G, dk)
    kernel = decode_query_attention if fused else decode_attention
    (k, v), scales = _cache_args(shape, KV, dk, dtype)

    def step(q, k, v, lengths, window, *scales):
        ks, vs = scales if scales else (None, None)
        return kernel(q, k, v, lengths, window=window, k_scale=ks,
                      v_scale=vs)

    text = _compiled_text(step, shape(q_shape, q_dtype), k, v,
                          shape((B,), jnp.int32), shape((), jnp.int32),
                          *scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_expected_attention_kernel_compiles(shape, width, dtype):
    """Compiled as compression runs it: vmapped over the layers of one
    item's cache."""
    KV, G, dk = WIDTHS[width]
    n_layers = 4
    stats = shape((n_layers, KV, G, dk), jnp.float32)
    text = _compiled_text(jax.vmap(expected_attention_scores),
                          shape((n_layers, 1, S, KV, dk), dtype), stats,
                          stats)
    assert "tpu_custom_call" in text
