"""Pure-jnp oracle for the latent chunk attention kernel: every key of the
cache decompressed, one float32 softmax over all of them, causally masked."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mla_chunk_attention_ref(
    q: jax.Array,  # [B,c,H,nope+rope], roped
    w_uk: jax.Array,  # [r,H,nope]
    w_uv: jax.Array,  # [r,H,v]
    c_kv: jax.Array,  # [B,T,r]
    k_rope: jax.Array,  # [B,T,rope]
    q_start: jax.Array,  # [B]
    *,
    scale: float,
) -> jax.Array:
    B, c, H, _ = q.shape
    T, nope = c_kv.shape[1], w_uk.shape[-1]
    k_nope = jnp.einsum("btr,rhe->bthe", c_kv, w_uk).astype(jnp.float32)
    v = jnp.einsum("btr,rhe->bthe", c_kv, w_uv).astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    s = jnp.einsum("bshe,bthe->bhst", q32[..., :nope], k_nope)
    s = (s + jnp.einsum("bshe,bte->bhst", q32[..., nope:], k_rope.astype(jnp.float32))) * scale
    qpos = q_start[:, None] + jnp.arange(c)[None, :]  # [B,c]
    mask = jnp.arange(T)[None, None, :] <= qpos[:, :, None]  # [B,c,T]
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhst,bthe->bshe", p, v)
    return out.reshape(B, c, -1).astype(q.dtype)
