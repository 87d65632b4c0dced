"""Beyond-paper perf features: chunked attention, streaming CE, MoE EP
annotations — correctness vs reference paths."""
import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.models.attention import chunked_gqa_attention, gqa_attention
from repro.models.layers import init_params
from repro.models.transformer import streaming_ce_loss
from repro.train import build_loss_fn, build_param_specs

CELL = ShapeCell("t", "train", {"seq_len": 64, "global_batch": 2})


def test_chunked_attention_bitexact_incl_grad():
    rng = np.random.default_rng(0)
    B, S, Hq, Hkv, d = 2, 96, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, Hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, d)), jnp.float32)
    for window, gf in [(None, None), (16, None), (16, jnp.asarray(0.0))]:
        a = gqa_attention(q, k, v, causal=True, window=window, global_flag=gf)
        b = chunked_gqa_attention(
            q, k, v, causal=True, window=window, global_flag=gf, block_q=32
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    g1 = jax.grad(lambda q: gqa_attention(q, k, v).sum())(q)
    g2 = jax.grad(lambda q: chunked_gqa_attention(q, k, v, block_q=32).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)


def test_streaming_ce_matches_dense_ce():
    rng = np.random.default_rng(1)
    B, S, d, V = 2, 8, 16, 96
    x = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(d, V)), jnp.float32)
    t = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    ref = -jnp.take_along_axis(
        jax.nn.log_softmax(x @ head, -1), t[..., None], -1
    )[..., 0].mean()
    for n in (1, 2, 4, 8):
        np.testing.assert_allclose(
            float(streaming_ce_loss(x, head, t, n)), float(ref), rtol=1e-6
        )


def test_lm_loss_vocab_chunks_equals_dense():
    cfg = get_config("tinyllama-1.1b", smoke=True)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), build_param_specs(cfg, CELL), jnp.float32)
    from repro.data import make_batch

    batch = make_batch(cfg, CELL, seed=0)
    dense = build_loss_fn(cfg, CELL)(params, batch)[0]
    cfg_c = dataclasses.replace(cfg, loss_vocab_chunks=8)
    chunked = build_loss_fn(cfg_c, CELL)(params, batch)[0]
    np.testing.assert_allclose(float(dense), float(chunked), rtol=1e-5)


def test_chunked_attention_impl_in_model_matches():
    cfg = get_config("gemma3-4b", smoke=True)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), build_param_specs(cfg, CELL), jnp.float32)
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, 48)), jnp.int32)
    from repro.models import transformer

    ref, _ = transformer.forward(params, cfg, tokens)
    cfg_c = dataclasses.replace(cfg, attention_impl="chunked", attn_block_q=16)
    out, _ = transformer.forward(params, cfg_c, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-4, rtol=1e-4)


def test_moe_ep_annotations_preserve_values():
    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), build_param_specs(cfg, CELL), jnp.float32)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)), jnp.int32)
    from repro.models import transformer

    ref, _ = transformer.forward(params, cfg, tokens)
    # single-device mesh: annotations must be value-neutral
    mesh = jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    cfg_a = dataclasses.replace(cfg, moe_ep_axis="model", moe_token_axes=("data",))
    with jax.set_mesh(mesh):
        out, _ = jax.jit(lambda p, t: transformer.forward(p, cfg_a, t))(params, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5)
