"""The prefill chunk's latent attention kernel (interpret mode) against the
model's XLA path, the absorbed form of ``mla_decode`` and the kernel's dense
reference.

Small widths with the served structure: a rope part apart from the nope
part, ``nope`` != ``v``, a YaRN-sized softmax scale.  In float32 at
precision ``highest`` the four forms differ only by the order of their
sums, so 1e-5 holds them together and a bfloat16 rounding breaks it (the
control)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import YarnSpec
from repro.kernels.mla_chunk_attention import mla_chunk_attention, mla_chunk_attention_ref
from repro.models.attention import mla_chunk_attend, mla_decode, mla_softmax_scale

H, R, NOPE, ROPE, V = 4, 32, 16, 8, 24
T, C, BLOCK_K = 64, 16, 16
SCALE = mla_softmax_scale(NOPE + ROPE, YarnSpec())  # DeepSeek-V2's YaRN: factor 40

# name -> (each row's q_start, the chunk's first position; the cache's max_len)
CASES = {
    "start_0": ([0], T),
    "start_off_block": ([13], T),  # not a key block's multiple: the edge spans two blocks
    "ends_on_block_edge": ([2 * BLOCK_K - C], T),  # live length exactly two key blocks
    "last_chunk": ([T - C], T),  # the chunk fills the cache to max_len
    "rows_differ": ([5, 40], T),  # B=2: each row visits its own live blocks
    # max_len not a multiple of the key block: the cache is padded to whole blocks
    "max_len_off_block": ([5, 60 - C], 60),
}


def _inputs(batch: int, dtype=jnp.float32, seed: int = 0, max_len: int = T):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    p = {"w_uk": jax.random.normal(k[0], (R, H, NOPE)) / 4,
         "w_uv": jax.random.normal(k[1], (R, H, V)) / 4}
    q = jax.random.normal(k[2], (batch, C, H, NOPE + ROPE)) * 2
    ckv = jax.random.normal(k[3], (batch, max_len, R))
    krope = jax.random.normal(k[4], (batch, max_len, ROPE))
    cast = lambda x: x.astype(dtype)  # noqa: E731
    return jax.tree_util.tree_map(cast, (q, p, ckv, krope))


def _kernel(q, p, ckv, krope, start):
    return mla_chunk_attention(q, p["w_uk"], p["w_uv"], ckv, krope, start, scale=SCALE,
                               block_k=BLOCK_K, interpret=True)


def _dense_ref(q, p, ckv, krope, start, *, scale):
    return mla_chunk_attention_ref(q, p["w_uk"], p["w_uv"], ckv, krope, start, scale=scale)


ORACLES = {"xla_blocks": mla_chunk_attend, "absorbed": mla_decode, "dense_ref": _dense_ref}


@pytest.mark.parametrize("oracle", sorted(ORACLES))
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_model_paths(case, oracle):
    starts, max_len = CASES[case]
    start = jnp.asarray(starts, jnp.int32)
    q, p, ckv, krope = _inputs(len(starts), max_len=max_len)
    attend = ORACLES[oracle]
    kw = {"block": BLOCK_K} if oracle == "xla_blocks" else {}
    with jax.default_matmul_precision("highest"):
        out = _kernel(q, p, ckv, krope, start)
        want = attend(q, p, ckv, krope, start, scale=SCALE, **kw)
    assert out.shape == (len(starts), C, H * V)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)
    control = np.asarray(out.astype(jnp.bfloat16).astype(jnp.float32))
    assert np.abs(control - np.asarray(want)).max() > 1e-5


def test_kernel_takes_a_short_cache_as_one_block():
    """A cache shorter than the default key block (1024) is one block, of
    the cache's own length: nothing is padded."""
    start = jnp.asarray([7, 44], jnp.int32)
    q, p, ckv, krope = _inputs(2, seed=4, max_len=T - 4)
    with jax.default_matmul_precision("highest"):
        out = mla_chunk_attention(q, p["w_uk"], p["w_uv"], ckv, krope, start, scale=SCALE,
                                  interpret=True)
        want = _dense_ref(q, p, ckv, krope, start, scale=SCALE)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_kernel_ignores_keys_past_the_chunk():
    """Keys past a row's live length (q_start + c) never count: the cache's
    rows beyond it may hold anything, as a reused cache's do."""
    start = jnp.asarray([13, 29], jnp.int32)
    q, p, ckv, krope = _inputs(2, seed=2)
    live = (start + C)[:, None, None]
    dead = jnp.arange(T)[None, :, None] >= live
    out = _kernel(q, p, ckv, krope, start)
    junk = _kernel(q, p, jnp.where(dead, 1e4, ckv), jnp.where(dead, -1e4, krope), start)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(junk))


def test_kernel_bfloat16_as_precise_as_xla_path():
    """In bfloat16, as served, the kernel lies no further from the float32
    answer than the XLA path does: its scores are never rounded to
    bfloat16, the XLA path's are."""
    start = jnp.asarray([13, 40], jnp.int32)
    q, p, ckv, krope = _inputs(2, jnp.bfloat16, seed=3)
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(mla_chunk_attend(*f32((q, p, ckv, krope)), start, scale=SCALE))
    err = {
        name: np.abs(np.asarray(out, np.float32) - exact).max()
        for name, out in (("kernel", _kernel(q, p, ckv, krope, start)),
                          ("xla", mla_chunk_attend(q, p, ckv, krope, start, scale=SCALE,
                                                   block=BLOCK_K)))
    }
    assert err["kernel"] <= err["xla"], err
    assert 1e-3 < err["kernel"] < 0.1, err
