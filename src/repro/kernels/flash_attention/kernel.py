"""Blocked flash attention (causal/local GQA) — pl.pallas_call + BlockSpec.

TPU mapping: grid (B, Hq, n_q_blocks, n_kv_blocks) with the KV dimension
innermost ("arbitrary" semantics — sequential on TPU), online-softmax
accumulators (m, l, acc) in VMEM scratch.  Operands are head-major,
[B, H, S, d], so block shapes are (block_q, d) / (block_k, d) tiles —
MXU-aligned multiples of 128 by default; Mosaic tiles the last two dims of a
block and refuses a (1, d) head slice — and the working set per step is
q + k + v + acc ~ 4 * 128 * d * 4B << VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    n_k: int,
    s_real: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]  # [blk_q, d]
    k = k_ref[...]  # [blk_k, d]
    v = v_ref[...]  # [blk_k, d]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * scale

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < s_real  # padded keys never contribute
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, MASK_VALUE)

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


def flash_attention_kernel(
    q: jax.Array,  # [B, Hq, Sq, d]  (padded to block multiples)
    k: jax.Array,  # [B, Hkv, Sk, d]
    v: jax.Array,
    *,
    causal: bool,
    window: int | None,
    s_real: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    n_q = Sq // block_q
    n_k = Sk // block_k
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
        s_real=s_real,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec(
                (None, None, block_k, d), lambda b, h, qi, ki: (b, h // group, ki, 0)
            ),
            pl.BlockSpec(
                (None, None, block_k, d), lambda b, h, qi, ki: (b, h // group, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, block_q, d), lambda b, h, qi, ki: (b, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
