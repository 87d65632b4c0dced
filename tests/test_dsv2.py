"""DeepSeek-V2 through the serving path, against the plain float32 reference
(``bench/lib/dsv2.py``) at a CPU size with seeded random weights.

Tolerances: the program and the reference both compute in float32 here, so
they differ only by the order of their sums (chunked prefill, the absorbed
decode, grouped matmuls): 2e-4 on logits of magnitude ~1-10 leaves room for
that and none for a bfloat16 rounding, which moves them by ~1e-2 (each test's
control shows it does).
"""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench" / "lib"))

import dsv2  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.attention import mla_chunk_attend, mla_decode, mla_softmax_scale  # noqa: E402
from repro.models.layers import yarn_freqs, yarn_mscale  # noqa: E402
from repro.models.moe import MoEDims, moe_ffn_dropless, route  # noqa: E402

TOL = 2e-4

HF = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "n_routed_experts": 4,
    "deployment": {"n_routed_experts": 8, "held_first": 2},
    "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2, "n_shared_experts": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": False, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "rope_scaling": {"factor": 4, "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 16,
                     "type": "yarn"},
}
DIMS = dsv2.Dims.from_hf(HF)
SEED = 2**33 + 17


def _served(dtype, chunk: int, prompt, forced):
    """Logits of every served position: the prompt prefilled chunk by chunk
    (``prefill_chunk``), then ``forced`` decoded one by one (``decode_step``),
    the programs ``ServeEngine.generate`` calls."""
    cfg = dsv2.program_config(DIMS, "dsv2-tiny", dtype)
    params = dsv2.program_params(SEED, DIMS, dtype)
    cache = transformer.init_cache(cfg, 1, 32)
    n = jnp.zeros((1,), jnp.int32)
    prefill = jax.jit(transformer.prefill_chunk, static_argnums=1)
    decode = jax.jit(transformer.decode_step, static_argnums=1)
    rows = []
    for i in range(0, len(prompt), chunk):
        tok = jnp.asarray(prompt[None, i : i + chunk])
        logits, cache, n = prefill(params, cfg, tok, cache, n)
    rows.append(logits[0])
    for t in forced[:-1]:
        logits, cache, n = decode(params, cfg, jnp.asarray([[t]]), cache, n)
        rows.append(logits[0])
    return np.asarray(jnp.stack(rows), np.float32)


@pytest.mark.parametrize("chunk", [1, 3, 24])
def test_step_programs_match_reference(chunk):
    """Prefill at chunk sizes 1, 3 and the whole prompt, then decode through
    the cache, give the reference's full-forward logits: no token is dropped
    at any chunk size (each is routed to every expert it picks).  Steps of
    few rows visit the experts they reached; the whole prompt's 72 rows take
    the grouped matmuls."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, DIMS.vocab, 24).astype(np.int32)
    forced = rng.integers(1, DIMS.vocab, 5).astype(np.int32)
    seq = np.concatenate([prompt, forced[:-1]])
    rows = np.arange(len(prompt) - 1, len(seq))
    with jax.default_matmul_precision("highest"):
        ref = dsv2.reference_logits(SEED, DIMS, [seq], [rows], dtype=jnp.float32, block_q=8)[0]
        got = _served(jnp.float32, chunk, prompt, forced)
        control = _served(jnp.bfloat16, chunk, prompt, forced)
    assert np.abs(got - ref).max() < TOL, np.abs(got - ref).max()
    assert np.abs(control - ref).max() > TOL  # bfloat16 weights and activations


def _planted(probs_logits):
    """A router that gives one token the planted logits."""
    d, E = len(probs_logits), len(probs_logits)
    h = jnp.zeros((1, d), jnp.float32).at[0, 0].set(1.0)
    router = jnp.zeros((d, E), jnp.float32).at[0].set(jnp.asarray(probs_logits))
    return h, router


def test_group_limited_routing_planted():
    """Published routing (160 experts, 8 groups, 3 kept, top-6): the fourth
    best group holds the fourth best expert, which plain top-6 would take."""
    cfg = get_config("deepseek-v2-236b")
    m = cfg.moe
    dims = MoEDims(n_experts=m.n_experts, top_k=m.top_k, n_shared=0, d_model=160, d_ff=8,
                   n_group=m.n_group, topk_group=m.topk_group, norm_topk=m.norm_topk,
                   routed_scale=m.routed_scale)
    logits = np.zeros(160, np.float32)
    best = {0: [10.0, 8.0, 7.0], 20: [9.9, 7.9], 40: [9.8, 7.8], 60: [9.7, 9.6, 9.5]}
    for start, vals in best.items():
        for i, v in enumerate(vals):
            logits[start + i] = v
    h, router = _planted(logits)
    gates, idx = route(h, router, dims)
    plain = set(np.argsort(-logits)[:6].tolist())
    assert 60 in plain and 61 in plain  # plain top-6 reaches the fourth group
    want = {0, 1, 20, 21, 40, 41}
    assert set(np.asarray(idx[0]).tolist()) == want
    probs = np.exp(logits - logits.max()) / np.exp(logits - logits.max()).sum()
    np.testing.assert_allclose(
        np.sort(np.asarray(gates[0])), np.sort(probs[sorted(want)] * 16.0), rtol=1e-5)
    ref_dims = dsv2.Dims(**{**DIMS.__dict__, "n_routed": 160, "n_group": 8, "topk_group": 3,
                            "top_k": 6, "routed_scale": 16.0})
    ref = np.asarray(dsv2.route(h, router, ref_dims))[0]
    assert set(np.nonzero(ref)[0].tolist()) == want
    np.testing.assert_allclose(ref[sorted(want)], probs[sorted(want)] * 16.0, rtol=1e-5)


def test_yarn_frequencies_and_mscale():
    """DeepSeek-V2's published YaRN: frequencies as HF's formula gives them,
    a cos/sin scale of 1 and a softmax scale of 192^-1/2 * mscale(40, .707)^2."""
    cfg = get_config("deepseek-v2-236b")
    y = cfg.rope_scaling
    got = np.asarray(yarn_freqs(64, cfg.rope_theta, y))
    dim, base = 64, 10000.0
    corr = lambda r: dim * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(base))  # noqa: E731
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got, dsv2.yarn_inv_freq(dsv2.Dims(**{
        **DIMS.__dict__, "rope": 64, "yarn": (40.0, 32.0, 1.0, 4096, 0.707, 0.707)})), rtol=1e-6)
    assert (low, high) == (10, 23)
    ms = 0.1 * 0.707 * math.log(40) + 1.0
    assert yarn_mscale(40, 0.707) == pytest.approx(ms)
    assert yarn_mscale(40, y.mscale) / yarn_mscale(40, y.mscale_all_dim) == 1.0
    assert mla_softmax_scale(192, y) == pytest.approx(192**-0.5 * ms**2, rel=1e-12)
    assert mla_softmax_scale(192, y) != pytest.approx(192**-0.5, rel=1e-3)  # plain scale


def test_expert_shares_sum_to_uncut_layer():
    """The routed parts that every share of the experts computes, with the
    shared experts counted once, add up to the uncut layer and to the
    reference's uncut layer."""
    full = dsv2.Dims(**{**DIMS.__dict__, "held_first": 0, "n_held": 8})
    w = dsv2.layer_weights(SEED, full, 1, jnp.float32)
    # 30 tokens x top-3: 90 rows take the grouped matmuls (FEW_ROWS is 64)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 30, full.d_model), jnp.float32)
    prog = dsv2._program_layers(w, dense=False)["moe"]

    def dims(first, n):
        return MoEDims(n_experts=8, top_k=3, n_shared=2, d_model=full.d_model, d_ff=32,
                       n_group=4, topk_group=2, norm_topk=False, routed_scale=2.5,
                       held_first=first, n_held=n)

    def share(first, n, shared):
        p = {k: prog[k][first : first + n] for k in ("w_gate", "w_up", "w_down")}
        p["router"] = prog["router"]
        if shared:
            p["shared"] = prog["shared"]
        return moe_ffn_dropless(h, p, dims(first, n))

    with jax.default_matmul_precision("highest"):
        uncut, hits = share(0, 8, True)
        parts = [share(f, 2, f == 0) for f in (0, 2, 4, 6)]
        total = sum(p[0] for p in parts)
        ref = dsv2.moe_ffn(h[0], w, full)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(uncut[0]), np.asarray(ref), atol=1e-5, rtol=1e-5)
    assert int(hits.sum()) == 90 and int(hits[-1]) == 0  # all held
    assert sum(int(p[1][:-1].sum()) for p in parts) == 90  # each assignment held once
    control = np.asarray(uncut.astype(jnp.bfloat16).astype(jnp.float32))
    assert np.abs(control - np.asarray(total)).max() > 1e-5


def test_chunk_attention_blocks_match_absorbed():
    """The prefill chunk's blocked, decompressed attention (several key
    blocks, online softmax) equals the absorbed form over the same cache."""
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    B, T, c, H, r, nope, rope, v = 1, 32, 5, 4, 16, 16, 8, 16
    p = {"w_uk": jax.random.normal(k[0], (r, H, nope)) / 4,
         "w_uv": jax.random.normal(k[1], (r, H, v)) / 4}
    q = jax.random.normal(k[2], (B, c, H, nope + rope))
    ckv = jax.random.normal(k[3], (B, T, r))
    krope = jax.random.normal(k[4], (B, T, rope))
    start = jnp.asarray([13], jnp.int32)
    with jax.default_matmul_precision("highest"):
        blocked = mla_chunk_attend(q, p, ckv, krope, start, scale=0.3, block=8)
        absorbed = mla_decode(q, p, ckv, krope, start, scale=0.3)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(absorbed), atol=1e-5, rtol=1e-5)
    control = np.asarray(blocked.astype(jnp.bfloat16).astype(jnp.float32))
    assert np.abs(control - np.asarray(absorbed)).max() > 1e-5
