"""The plain float32 reference against the program's own forward pass, at a
small width on the CPU, and the weights the two are given."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtiny  # noqa: F401  (puts the benchmark's library on the path)
import lm

from repro.configs.base import LMConfig
from repro.models import transformer

DIMS = lm.Dims(n_layers=3, d_model=128, n_heads=4, n_kv_heads=2, d_head=32, d_ff=256,
               vocab=512, rms_eps=1e-6, rope_theta=10000.0)
SEED = 2**31 + 101


def _cfg(dtype):
    return LMConfig(name="t", n_layers=DIMS.n_layers, d_model=DIMS.d_model,
                    n_heads=DIMS.n_heads, n_kv_heads=DIMS.n_kv_heads, d_head=DIMS.d_head,
                    d_ff=DIMS.d_ff, vocab=DIMS.vocab, rope_theta=DIMS.rope_theta, dtype=dtype)


def test_program_params_are_the_layers_the_reference_draws():
    params = lm.program_params(SEED, DIMS, jnp.bfloat16)
    for layer in (0, DIMS.n_layers - 1):
        w = lm.layer_weights(SEED, DIMS, layer)
        got = {**params["layers"]["attn"], **params["layers"]["ffn"],
               "ln1": params["layers"]["ln1"], "ln2": params["layers"]["ln2"]}
        for name, arr in w.items():
            assert np.array_equal(np.asarray(got[name][layer]), np.asarray(arr)), name
    top = lm.top_weights(SEED, DIMS)
    for name in ("embed", "final_norm", "head"):
        assert np.array_equal(np.asarray(params[name]), np.asarray(top[name])), name


def test_reference_matches_the_program_forward_in_float32():
    params = lm.program_params(SEED, DIMS, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, DIMS.vocab, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(transformer.forward(params, _cfg(jnp.float32), jnp.asarray(tokens[None]))[0][0])
        got = lm.reference_logits(SEED, DIMS, [tokens], [np.arange(40)], length=48,
                                  dtype=jnp.float32)[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4 * np.abs(want).max())


def test_padding_past_the_sequence_changes_nothing():
    tokens = np.arange(1, 21, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        a = lm.reference_logits(SEED, DIMS, [tokens], [np.arange(20)], length=20, dtype=jnp.float32)[0]
        b = lm.reference_logits(SEED, DIMS, [tokens], [np.arange(20)], length=64, dtype=jnp.float32)[0]
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_float8_control_departs_from_the_reference():
    tokens = np.random.default_rng(1).integers(0, DIMS.vocab, 32).astype(np.int32)
    rows = [np.arange(32)]
    with jax.default_matmul_precision("highest"):
        ref = lm.reference_logits(SEED, DIMS, [tokens], rows, dtype=jnp.float32)[0]
        low = lm.reference_logits(SEED, DIMS, [tokens], rows, dtype=jnp.float32, quant=True)[0]
    rel = np.abs(low - ref).max() / np.abs(ref).max()
    assert 1e-3 < rel < 0.5
