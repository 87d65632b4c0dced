"""jit'd wrapper: the scale folded into the queries, the head-major query
layout, the flattened up-projections and the cache padded to whole key
blocks."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import mla_chunk_attention_kernel


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def mla_chunk_attention(
    q: jax.Array,  # [B,c,H,nope+rope], roped
    w_uk: jax.Array,  # [r,H,nope]
    w_uv: jax.Array,  # [r,H,v]
    c_kv: jax.Array,  # [B,T,r]   (this chunk's latents already written)
    k_rope: jax.Array,  # [B,T,rope]
    q_start: jax.Array,  # [B] position of the chunk's first token
    *,
    scale: float,
    block_k: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Decompressed latent attention of a prefill chunk against the cache:
    query s of row b attends keys 0..q_start[b]+s.  Returns [B,c,H*v].

    The cache is visited in key blocks of ``block_k`` (the fastest of those
    tried on a TPU v5e at DeepSeek-V2's serving widths); a cache of at most
    one block is one block, and a longer one whose length is not a multiple
    is padded with zeros, which lie past every live key."""
    T = c_kv.shape[1]
    r = w_uk.shape[0]
    block_k = min(block_k, T)
    # the scale, folded into the queries once; head-major
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype).transpose(0, 2, 1, 3)
    return mla_chunk_attention_kernel(
        q_start.astype(jnp.int32),
        qs,
        _pad_to(c_kv, 1, block_k),
        _pad_to(k_rope, 1, block_k),
        w_uk.reshape(r, -1),
        w_uv.reshape(r, -1),
        block_k=block_k,
        interpret=interpret,
    )
