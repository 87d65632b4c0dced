"""Seeded weights and the plain float32 reference for a dense decoder LM.

The benchmark owns the weights: they are drawn here from ``--seed``, one
layer at a time from its own key, so the reference can draw any layer again
without taking anything from the program under test.  The equations are those
of the published DeepSeek-LLM 7B (a pre-norm decoder: RMSNorm, rotary
embeddings on halves of each head, softmax attention, SwiGLU feed-forward,
an untied output head), written out in ``jax.numpy`` with no cache, no
chunks and no kernels.

Each RMSNorm weight is ``1 + delta``: the draw is the offset ``delta``, which
is what the program's parameter tree stores.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Dims:
    """Sizes of a dense decoder, read from a Hugging Face ``config.json``."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rms_eps: float
    rope_theta: float

    @classmethod
    def from_hf(cls, cfg: dict) -> "Dims":
        heads = cfg["num_attention_heads"]
        return cls(
            n_layers=cfg["num_hidden_layers"],
            d_model=cfg["hidden_size"],
            n_heads=heads,
            n_kv_heads=cfg.get("num_key_value_heads", heads),
            d_head=cfg.get("head_dim", cfg["hidden_size"] // heads),
            d_ff=cfg["intermediate_size"],
            vocab=cfg["vocab_size"],
            rms_eps=cfg["rms_norm_eps"],
            rope_theta=cfg["rope_theta"],
        )


NORM_STD = 0.1  # spread of the RMSNorm weights around 1
EMBED_STD = 1.0


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, including those over 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _layer_shapes(m: Dims) -> dict[str, tuple[tuple[int, ...], float]]:
    d, h, kv, e, f = m.d_model, m.n_heads, m.n_kv_heads, m.d_head, m.d_ff
    return {
        "ln1": ((d,), NORM_STD),
        "ln2": ((d,), NORM_STD),
        "wq": ((d, h, e), d**-0.5),
        "wk": ((d, kv, e), d**-0.5),
        "wv": ((d, kv, e), d**-0.5),
        "wo": ((h, e, d), (h * e) ** -0.5),
        "w_gate": ((d, f), d**-0.5),
        "w_up": ((d, f), d**-0.5),
        "w_down": ((f, d), f**-0.5),
    }


def _draw(key, shapes: dict, dtype) -> dict[str, jax.Array]:
    keys = jax.random.split(key, len(shapes))
    return {
        name: (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        for k, (name, (shape, std)) in zip(keys, shapes.items())
    }


def layer_weights(seed: int, m: Dims, layer: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``layer``'s weights, the same values the program is given."""
    key = jax.random.fold_in(jax.random.fold_in(root_key(seed), 1), layer)
    return _draw_jit(key, m, dtype)


@partial(jax.jit, static_argnames=("m", "dtype"))
def _draw_jit(key, m: Dims, dtype):
    return _draw(key, _layer_shapes(m), dtype)


def _top_shapes(m: Dims) -> dict[str, tuple[tuple[int, ...], float]]:
    d, v = m.d_model, m.vocab
    return {
        "embed": ((v, d), EMBED_STD),
        "final_norm": ((d,), NORM_STD),
        "head": ((d, v), d**-0.5),
    }


def top_weights(seed: int, m: Dims, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and output head."""
    return _draw_top_jit(jax.random.fold_in(root_key(seed), 0), m, dtype)


@partial(jax.jit, static_argnames=("m", "dtype"))
def _draw_top_jit(key, m: Dims, dtype):
    return _draw(key, _top_shapes(m), dtype)


def program_params(seed: int, m: Dims, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout ``repro.models.transformer``
    takes, drawn on the device in one jitted call."""

    @jax.jit
    def build(key):
        top = _draw(jax.random.fold_in(key, 0), _top_shapes(m), dtype)
        layer_keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.fold_in(key, 1), i))(
            jnp.arange(m.n_layers)
        )
        ly = jax.vmap(lambda k: _draw(k, _layer_shapes(m), dtype))(layer_keys)
        return {
            "embed": top["embed"],
            "final_norm": top["final_norm"],
            "head": top["head"],
            "layers": {
                "ln1": ly["ln1"],
                "ln2": ly["ln2"],
                "attn": {n: ly[n] for n in ("wq", "wk", "wv", "wo")},
                "ffn": {n: ly[n] for n in ("w_gate", "w_up", "w_down")},
            },
        }

    return build(root_key(seed))


# -- the reference -------------------------------------------------------------
# ``quant=True`` is the control: every matrix product takes operands rounded to
# float8 (e4m3, scaled by the absolute maximum of each row of activations and
# of each weight tensor), the precision below the bfloat16 the model is served
# in.  The reference proper computes in float32 at the highest matmul precision.
def _fp8(x: jax.Array, axis) -> jax.Array:
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, quant: bool, a_axis=-1, b_axis=None):
    if quant:
        a = _fp8(a, a_axis)
        b = _fp8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, delta, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + delta)


def _rope(x, positions, theta):
    """Rotary embedding on the two halves of each head: x [S, H, e]."""
    e = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : e // 2], x[..., e // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, w, m: Dims, quant: bool):
    """One decoder layer over x [S, d] (float32), causal over all S rows."""
    S = x.shape[0]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], m.rms_eps)
    q = _rope(_mm("sd,dhe->she", h, w["wq"], quant), pos, m.rope_theta)
    k = _rope(_mm("sd,dhe->she", h, w["wk"], quant), pos, m.rope_theta)
    v = _mm("sd,dhe->she", h, w["wv"], quant)
    group = m.n_heads // m.n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = _mm("she,the->hst", q, k, quant, a_axis=-1, b_axis=-1) / np.sqrt(m.d_head)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = _mm("hst,the->she", p, v, quant, a_axis=-1, b_axis=0)
    x = x + _mm("she,hed->sd", o, w["wo"], quant, a_axis=(1, 2))
    h = _rms(x, w["ln2"], m.rms_eps)
    g = jax.nn.silu(_mm("sd,df->sf", h, w["w_gate"], quant))
    u = _mm("sd,df->sf", h, w["w_up"], quant)
    return x + _mm("sf,fd->sd", g * u, w["w_down"], quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, rows, top, m: Dims, quant: bool):
    h = _rms(x[rows], top["final_norm"].astype(jnp.float32), m.rms_eps)
    return _mm("sd,dv->sv", h, top["head"].astype(jnp.float32), quant)


def reference_logits(
    seed: int,
    m: Dims,
    sequences: list[np.ndarray],
    rows: list[np.ndarray],
    *,
    quant: bool = False,
    length: int | None = None,
    dtype=jnp.bfloat16,
) -> list[np.ndarray]:
    """Logits [len(rows[i]), vocab] at positions ``rows[i]`` of each sequence.

    Every sequence is right-padded with token 0 to ``length`` (default: the
    longest), which causal attention never lets an earlier row see, so one
    compiled layer serves all.  The weights are drawn again layer by layer,
    so at most one layer's weights are live at a time.  ``dtype`` is the one
    the weights are served in: the reference computes on those values."""
    length = length or max(len(s) for s in sequences)
    top = top_weights(seed, m, dtype)
    xs = []
    for s in sequences:
        tok = np.zeros(length, np.int32)
        tok[: len(s)] = s
        xs.append(top["embed"][jnp.asarray(tok)].astype(jnp.float32))
    for layer in range(m.n_layers):
        w = layer_weights(seed, m, layer, dtype)
        xs = [_layer(x, w, m, quant) for x in xs]
        del w
    out = [
        np.asarray(_head(x, jnp.asarray(r, jnp.int32), top, m, quant))
        for x, r in zip(xs, rows)
    ]
    return out
