"""jit'd wrapper: padding, GQA plumbing and the head-major layout."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_kernel


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B,Sq,Hq,d]
    k: jax.Array,  # [B,Sk,Hkv,d]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, Hq, d = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, max(Sq, 16))
    block_k = min(block_k, max(Sk, 16))
    # head-major [B, H, S, d]: the kernel's blocks are (block, d) tiles
    qp = _pad_to(q, 1, block_q).transpose(0, 2, 1, 3)
    kp = _pad_to(k, 1, block_k).transpose(0, 2, 1, 3)
    vp = _pad_to(v, 1, block_k).transpose(0, 2, 1, 3)
    out = flash_attention_kernel(
        qp,
        kp,
        vp,
        causal=causal,
        window=window,
        s_real=Sk,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)[:, :Sq]
