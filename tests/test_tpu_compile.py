"""Compile the serving path for a TPU v5e that is described, not attached.

What the chip's compiler refuses here costs no chip time: the DeepSeek-7B
step programs at published widths (depth cut to 2 layers), the attention
kernels at serving widths, and DeepSeek-V2's prefill latent attention as
``ServeEngine`` lowers it for a TPU.  Nothing runs, so these say nothing of
results or times.  The topology is described inside a fixture, never at
import: only one process may load the TPU library, and the xdist worker
given this file is the one that does.
"""
import dataclasses
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import YarnSpec
from repro.kernels import decode_attention, flash_attention
from repro.models import transformer
from repro.models.attention import mla_chunk_attend, mla_softmax_scale
from repro.models.layers import abstract_params

BENCH = Path(__file__).resolve().parents[1] / "bench"

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


def _compile_mla_attend(one_chip, max_len: int) -> str:
    """``mla_chunk_attend`` compiled for the described v5e at DeepSeek-V2's
    serving widths (128 heads, latent 512, nope 128 + rope 64, v 128,
    chunk 1024): the compiled HLO's text."""
    H, r, nope, rope, v, c = 128, 512, 128, 64, 128, 1024
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    scale = mla_softmax_scale(nope + rope, YarnSpec())

    def attend(q, w_uk, w_uv, ckv, krope, start):
        p = {"w_uk": w_uk, "w_uv": w_uv}
        return mla_chunk_attend(q, p, ckv, krope, start, scale=scale)

    return jax.jit(attend).lower(
        sds(1, c, H, nope + rope), sds(r, H, nope), sds(r, H, v), sds(1, max_len, r),
        sds(1, max_len, rope), sds(1, dtype=jnp.int32),
    ).compile().as_text()


def _custom_calls(text: str) -> list[str]:
    return [line.split(" = ")[0].split()[-1].lstrip("%")
            for line in text.splitlines() if "tpu_custom_call" in line]


def test_mla_chunk_attention_compiles_in_scope(one_chip):
    """DeepSeek-V2's prefill chunk attention at the served max_len 16384:
    lowered for a TPU, ``mla_chunk_attend`` is the Pallas kernel, and the
    benchmark's HLO reader gives the kernel's custom call to the
    ``mla.attend`` scope, whose device time ``mla_attention_roofline``
    reads."""
    if str(BENCH / "lib") not in sys.path:
        sys.path.insert(0, str(BENCH / "lib"))
    import harness

    hlo_scopes = harness.load_module(BENCH / "drivers" / "serve_dsv2.py").hlo_scopes
    text = _compile_mla_attend(one_chip, 16384)
    calls = _custom_calls(text)
    assert len(calls) == 1, calls
    assert hlo_scopes(text).get(calls[0]) == "mla.attend"


def test_mla_chunk_attention_compiles_off_block_max_len(one_chip):
    """A max_len that is not a multiple of the kernel's 1024-key block
    (the engine's max_len is its caller's to set): the kernel takes the
    cache padded to whole blocks, 10240 keys, so its VMEM holds one
    1024-key block and it visits only the live ones, never the whole cache
    as one block."""
    text = _compile_mla_attend(one_chip, 10000)
    lines = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(lines) == 1, lines
    assert "bf16[1,10240,512]" in lines[0] and "bf16[1,10240,64]" in lines[0], lines[0][:600]
