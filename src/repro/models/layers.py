"""Common layers + the param-spec system (init / logical axes / abstract).

Params are nested dicts of jnp arrays.  Every model declares a *spec tree* of
``PSpec`` (shape, logical axes, init); from one spec tree we derive:

  init_params     — materialized params (smoke tests, real training)
  abstract_params — ShapeDtypeStructs with shardings (dry-run: no allocation)
  logical_axes    — the axes tree consumed by launch.sharding.resolve
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# param specs
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class PSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"  # normal | zeros | ones | embed
    scale: float | None = None  # default: 1/sqrt(fan_in) for normal

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x: Any) -> bool:
    return isinstance(x, PSpec)


def init_params(key: jax.Array, specs: Any, dtype: Any) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, s in zip(keys, leaves):
        if s.init == "zeros":
            out.append(jnp.zeros(s.shape, dtype))
        elif s.init == "ones":
            out.append(jnp.ones(s.shape, dtype))
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else max(s.shape[-1], 1)
            scale = s.scale if s.scale is not None else 1.0 / math.sqrt(fan_in)
            out.append((jax.random.normal(k, s.shape) * scale).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(specs: Any, dtype: Any, shardings: Any = None) -> Any:
    """ShapeDtypeStructs (optionally with shardings) — dry-run stand-ins."""
    if shardings is None:
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs, is_leaf=_is_spec
        )
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, dtype, sharding=sh),
        specs,
        shardings,
        is_leaf=_is_spec,
    )


def logical_axes(specs: Any) -> Any:
    return jax.tree_util.tree_map(lambda s: s.axes, specs, is_leaf=_is_spec)


def param_count(specs: Any) -> int:
    return sum(
        math.prod(s.shape)
        for s in jax.tree_util.tree_leaves(specs, is_leaf=_is_spec)
    )


# --------------------------------------------------------------------------
# functional layers
# --------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def batch_stat_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5):
    """BatchNorm with on-the-fly batch statistics (training mode, no EMA).

    GatedGCN's reference uses BN; a pure-functional train step computes batch
    stats per step.  Stats reduce over all leading dims.
    """
    xf = x.astype(jnp.float32)
    red = tuple(range(x.ndim - 1))
    mu = jnp.mean(xf, axis=red, keepdims=True)
    var = jnp.var(xf, axis=red, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array):
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


# -- rotary ------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0):
    """x: [..., S, H, dh]; positions: [..., S]."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # [dh/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, dh/2]
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(scale) + 1."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(head_dim: int, theta: float, yarn) -> jax.Array:
    """Inverse frequencies of HF ``DeepseekV2YarnRotaryEmbedding``: the low
    dimensions keep theta's frequencies, the high ones are divided by
    ``factor``, with a linear ramp between the correction range that
    ``beta_fast`` and ``beta_slow`` rotations over the original context set."""
    def corr_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position / (rotations * 2 * math.pi))
                ) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1 where the original frequency is kept
    return extra / yarn.factor * (1.0 - keep) + extra * keep


def apply_rope_interleaved(x: jax.Array, positions: jax.Array, theta: float, yarn=None):
    """DeepSeek's rope: x [..., S, H, dh] holds its rotary pairs interleaved
    (x0,x1),(x2,x3)...; they are permuted to halves, then rotated as
    ``apply_rope`` does, with YaRN frequencies and cos/sin scale where
    ``yarn`` is given."""
    dh = x.shape[-1]
    x = x.reshape(*x.shape[:-1], dh // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*x.shape[:-2], dh)
    if yarn is None:
        freqs, scale = rope_freqs(dh, theta), 1.0
    else:
        freqs = yarn_freqs(dh, theta, yarn)
        scale = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(
            yarn.factor, yarn.mscale_all_dim)
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., :, None, :] * scale
    sin = jnp.sin(angles)[..., :, None, :] * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- embedding / segment ops ---------------------------------------------------
def embedding_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0)


def embedding_bag(
    table: jax.Array,
    ids: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    combiner: str = "sum",
    weights: jax.Array | None = None,
) -> jax.Array:
    """EmbeddingBag via gather + segment_sum (JAX has no native one).

    ids: [nnz] row indices; segment_ids: [nnz] output bag per id (sorted not
    required); returns [num_segments, dim].
    """
    rows = jnp.take(table, ids, axis=0)
    if weights is not None:
        rows = rows * weights[:, None]
    out = jax.ops.segment_sum(rows, segment_ids, num_segments=num_segments)
    if combiner == "mean":
        cnt = jax.ops.segment_sum(
            jnp.ones_like(segment_ids, dtype=rows.dtype),
            segment_ids,
            num_segments=num_segments,
        )
        out = out / jnp.maximum(cnt, 1.0)[:, None]
    return out


def mlp(x: jax.Array, layers: list[dict[str, jax.Array]], act=jax.nn.relu):
    for i, lyr in enumerate(layers):
        x = jnp.einsum("...d,df->...f", x, lyr["w"]) + lyr["b"]
        if i < len(layers) - 1:
            x = act(x)
    return x


def mlp_specs(d_in: int, widths: tuple[int, ...], axes_in="embed", prefix="mlp"):
    layers = []
    d = d_in
    for i, w in enumerate(widths):
        layers.append(
            {
                "w": PSpec((d, w), (axes_in if i == 0 else "mlp_hidden", "mlp_hidden")),
                "b": PSpec((w,), ("mlp_hidden",), init="zeros"),
            }
        )
        d = w
    return layers
