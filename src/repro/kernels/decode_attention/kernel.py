"""Cached decode attention (flash-decoding style) — one query token against a
long KV cache, online softmax over KV blocks, variable per-sequence lengths
delivered via scalar prefetch (SMEM on TPU).

Grid (B, Hkv, n_kv_blocks): KV innermost/sequential; the per-(b,h) state is
the grouped-query accumulator (G, d) in VMEM scratch.  The cache is taken
head-major, [B, Hkv, T, d], so each block's last two dims are (block_k, d):
Mosaic tiles the last two dims of a block and refuses a (1, d) head slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_kernel(
    lens_ref,  # scalar-prefetch: [B] int32 (valid length incl. current token)
    q_ref,  # [G, d]
    k_ref,  # [blk_k, d]
    v_ref,
    o_ref,  # [G, d]
    m_scr,  # [G]
    l_scr,  # [G]
    acc_scr,  # [G, d]
    *,
    scale: float,
    block_k: int,
    n_k: int,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * scale  # [G, blk_k]
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < lens_ref[b], s, MASK_VALUE)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
    m_scr[...] = m_cur
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def decode_attention_kernel(
    q: jax.Array,  # [B, Hkv, G, d]
    k_cache: jax.Array,  # [B, Hkv, T, d]  (T padded to block multiple)
    v_cache: jax.Array,
    lens: jax.Array,  # [B] int32
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, G, d = q.shape
    T = k_cache.shape[2]
    n_k = T // block_k
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, n_k=n_k
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, n_k),
        in_specs=[
            pl.BlockSpec((None, None, G, d), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec(
                (None, None, block_k, d), lambda b, h, ki, lens: (b, h, ki, 0)
            ),
            pl.BlockSpec(
                (None, None, block_k, d), lambda b, h, ki, lens: (b, h, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, G, d), lambda b, h, ki, lens: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, q, k_cache, v_cache)
