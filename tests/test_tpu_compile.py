"""Compile the serving path for a TPU v5e that is described, not attached.

What the chip's compiler refuses here costs no chip time: the DeepSeek-7B
step programs at published widths (depth cut to 2 layers) and the attention
kernels at serving widths.  Nothing runs, so these say nothing of results or
times.  The topology is described inside a fixture, never at import: only
one process may load the TPU library, and the xdist worker given this file
is the one that does.
"""
import dataclasses

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention, flash_attention
from repro.models import transformer
from repro.models.layers import abstract_params

MAX_LEN = 4096
HEADS, HEAD_DIM, POSITIONS = 32, 128, 2048  # DeepSeek-7B serving widths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # else libtpu writes log files under /tmp and $TMPDIR; compile
        # errors still reach the test as exceptions
        mp.setenv("TPU_LOG_DIR", "disabled")
        mp.setenv("TPU_MIN_LOG_LEVEL", "3")
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off: a program compiled
    for a described chip is written to the cache but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


@pytest.mark.parametrize("step", ["prefill_chunk", "decode_step"])
def test_serving_step_compiles(one_chip, step):
    cfg = dataclasses.replace(get_config("deepseek-7b"), n_layers=2)
    params = _on(one_chip, abstract_params(transformer.lm_specs(cfg), cfg.dtype))
    cache = _on(one_chip, jax.eval_shape(lambda: transformer.init_cache(cfg, 1, MAX_LEN)))
    c = 256 if step == "prefill_chunk" else 1
    tokens = jax.ShapeDtypeStruct((1, c), jnp.int32, sharding=one_chip)
    cache_len = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    fn = getattr(transformer, step)
    compiled = (
        jax.jit(lambda p, t, kv, n: fn(p, cfg, t, kv, n))
        .lower(params, tokens, cache, cache_len)
        .compile()
    )
    cache_bytes = 2 * 2 * MAX_LEN * cfg.n_kv_heads * cfg.head_dim * 2  # k+v, bf16
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= cache_bytes  # a whole new cache comes out


@pytest.mark.parametrize("kernel", ["decode_attention", "flash_attention"])
def test_attention_kernel_compiles(one_chip, kernel):
    bf16 = jnp.bfloat16
    kv = jax.ShapeDtypeStruct((1, POSITIONS, HEADS, HEAD_DIM), bf16, sharding=one_chip)
    if kernel == "decode_attention":
        q = jax.ShapeDtypeStruct((1, HEADS, HEAD_DIM), bf16, sharding=one_chip)
        lens = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
        lowered = jax.jit(decode_attention).lower(q, kv, kv, lens)
    else:
        lowered = jax.jit(lambda q, k, v: flash_attention(q, k, v)).lower(kv, kv, kv)
    assert "tpu_custom_call" in lowered.compile().as_text()
