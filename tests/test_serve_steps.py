"""The serving engine's one cached step and its one cache writer, on a tiny
dense GQA model and a tiny latent-attention model with routed experts.

* A full-prefix hit steps the cache back one chunk and runs that chunk
  again, overwriting its rows: the cache, the first logits and the served
  tokens equal a cold engine's, bit for bit.
* A request whose left-padded prompt and new tokens would not fit
  ``max_len`` is refused before any step runs or the policy sees it.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench" / "lib"))

import dsv2  # noqa: E402
from test_dsv2 import DIMS, SEED  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.risp import TSAR  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.layers import init_params  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402

CHUNK, MAX_LEN = 8, 48


def _model(kind):
    if kind == "gqa":
        cfg = get_config("tinyllama-1.1b", smoke=True)
        return cfg, init_params(jax.random.PRNGKey(3), transformer.lm_specs(cfg), cfg.dtype)
    cfg = dsv2.program_config(DIMS, "dsv2-tiny", jnp.float32)
    return cfg, dsv2.program_params(SEED, DIMS, jnp.float32)


@pytest.fixture(scope="module", params=["gqa", "mla_moe"])
def model(request):
    return _model(request.param)


def _recording(engine):
    """Record the logits of each prefill step and the state the first decode
    step is given: the cache and length the prompt left behind."""
    seen = {"logits": None, "cache": None, "len": None}
    prefill, decode = engine._prefill, engine._decode

    def on_prefill(*a):
        out = prefill(*a)
        seen["logits"] = out[0]
        return out

    def on_decode(p, t, c, l, *rest):
        if seen["cache"] is None:
            seen["cache"], seen["len"] = c, int(l[0])
        return decode(p, t, c, l, *rest)

    engine._prefill, engine._decode = on_prefill, on_decode
    return seen


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint8 if a.dtype.itemsize == 1 else f"u{a.dtype.itemsize}")


def test_full_hit_equals_cold_bit_for_bit(model):
    cfg, params = model
    prompt = np.random.default_rng(5).integers(1, cfg.vocab, 3 * CHUNK - 3).tolist()

    cold = ServeEngine(cfg, params, max_len=MAX_LEN, chunk=CHUNK)
    want = _recording(cold)
    ref, st = cold.generate(prompt, max_new_tokens=4)
    assert st.chunks_skipped == 0

    eng = ServeEngine(cfg, params, max_len=MAX_LEN, chunk=CHUNK, policy=TSAR())
    eng.generate(prompt, max_new_tokens=4)  # stores the whole prompt's snapshot
    got = _recording(eng)
    toks, st = eng.generate(prompt, max_new_tokens=4)
    assert st.chunks_skipped == st.n_chunks == 3  # full-prefix hit

    assert toks == ref
    np.testing.assert_array_equal(_bits(got["logits"]), _bits(want["logits"]))
    assert got["len"] == want["len"] == 3 * CHUNK
    n = want["len"]
    leaves = zip(jax.tree_util.tree_leaves(got["cache"]),
                 jax.tree_util.tree_leaves(want["cache"]))
    for a, b in leaves:  # [L, B, max_len, ...]: the rows the prompt wrote
        np.testing.assert_array_equal(_bits(a[:, :, :n]), _bits(b[:, :, :n]))


def test_request_past_max_len_is_refused(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, chunk=CHUNK)
    steps = []
    prefill = eng._prefill
    eng._prefill = lambda *a: steps.append(1) or prefill(*a)
    prompt = list(range(1, 2 * CHUNK + 4))  # left-padded to 3 chunks: 24 rows

    with pytest.raises(ValueError, match="max_len"):
        eng.generate(prompt, max_new_tokens=MAX_LEN - 3 * CHUNK + 1)
    assert steps == [] and eng.policy.n_pipelines == 0
    assert eng.aggregate_stats().runs == 0

    toks, st = eng.generate(prompt, max_new_tokens=MAX_LEN - 3 * CHUNK)  # fills it exactly
    assert len(toks) == MAX_LEN - 3 * CHUNK and st.n_chunks == 3
    assert eng.policy.n_pipelines == 1
