"""jit'd wrapper for the decode attention kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import decode_attention_kernel


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jax.Array,  # [B, Hq, d] — single query token per sequence
    k_cache: jax.Array,  # [B, T, Hkv, d]
    v_cache: jax.Array,
    lens: jax.Array,  # [B] valid cache length incl. current token
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, d = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    block_k = min(block_k, T)
    pad = (-T) % block_k
    # head-major [B, Hkv, T, d]: the kernel's blocks are (block_k, d) tiles
    k_cache, v_cache = (
        jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)]).transpose(0, 2, 1, 3)
        for x in (k_cache, v_cache)
    )
    qg = q.reshape(B, Hkv, G, d)
    out = decode_attention_kernel(
        qg, k_cache, v_cache, lens.astype(jnp.int32),
        block_k=block_k, interpret=interpret,
    )
    return out.reshape(B, Hq, d)
