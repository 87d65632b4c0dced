"""Cached latent attention for one prefill chunk (DeepSeek-V2's MLA) —
pl.pallas_call over the layer's latent cache.

The whole chunk is one query block, so each live key is decompressed once
per chunk.  Grid (B, head groups, key blocks), the key blocks innermost
("arbitrary": sequential on TPU).  A step loads one latent block
``c_kv [block_k, r]`` and its shared rope keys, decompresses the group's
keys and values in VMEM (``c_kv @ w_uk``, ``c_kv @ w_uv``), and for each
head folds the chunk's scores into the online-softmax state (m, l, acc)
held in float32 VMEM scratch: no score tile reaches HBM.

Only live blocks are visited: ``q_start`` comes by scalar prefetch, the
latent blocks' index map is clamped to the last block that holds a key of
the chunk (so no dead block is copied in: an unchanged block index issues
no DMA), and the steps past it do nothing.  Only the blocks on the chunk's
causal edge are masked.  Each step runs one branch of straight-line code
over its heads (with a branch per head and query tile the kernel was ~15%
slower on a TPU v5e).

Each grid step takes ``HEADS_PER_STEP`` heads (fewer where the head count
is not a multiple): the fastest grouping of those tried on a TPU v5e at
DeepSeek-V2's serving widths (128 heads, chunk 1024, 1024-key blocks).

Operands: queries head-major ``[B, H, c, nope+rope]`` with the softmax scale
folded in, the up-projections flattened to ``[r, H*nope]`` / ``[r, H*v]``
(a group's columns are one lane-aligned block), and the output
``[B, c, H*v]`` as the model's output projection takes it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEADS_PER_STEP = 2
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
VMEM_LIMIT_BYTES = 100 * 2**20  # of v5e's 128 MiB; the default scoped limit is 16 MiB
_NT = (((1,), (1,)), ((), ()))  # contract the last dims: q @ k.T
_NN = (((1,), (0,)), ((), ()))


def _mla_chunk_kernel(
    qs_ref,  # scalar prefetch: [B] int32, position of each row's first query
    q_ref,  # [hg, c, nope+rope]  scaled
    ckv_ref,  # [block_k, r]
    kr_ref,  # [block_k, rope]
    wuk_ref,  # [r, hg*nope]
    wuv_ref,  # [r, hg*v]
    o_ref,  # [c, hg*v]
    m_scr,  # [hg, c, 1] f32
    l_scr,  # [hg, c, 1] f32
    acc_scr,  # [hg, c, v] f32
):
    hg, c, _ = q_ref.shape
    block_k = ckv_ref.shape[0]
    nope = wuk_ref.shape[1] // hg
    v_dim = acc_scr.shape[-1]
    kb = pl.program_id(2)
    q_start = qs_ref[pl.program_id(0)]
    last = (q_start + c - 1) // block_k  # the last block holding a key of the chunk
    k_lo = kb * block_k

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def visit(edge: bool):
        ckv = ckv_ref[...]
        k_nope = jax.lax.dot_general(
            ckv, wuk_ref[...], _NN, preferred_element_type=jnp.float32
        ).astype(ckv.dtype)  # [block_k, hg*nope]
        v = jax.lax.dot_general(
            ckv, wuv_ref[...], _NN, preferred_element_type=jnp.float32
        ).astype(ckv.dtype)  # [block_k, hg*v]
        k_rope = kr_ref[...]
        if edge:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (c, block_k), 0)
            visible = k_lo + jax.lax.broadcasted_iota(jnp.int32, (c, block_k), 1) <= qpos
        for h in range(hg):
            k = jnp.concatenate([k_nope[:, h * nope:(h + 1) * nope], k_rope], axis=1)
            s = jax.lax.dot_general(q_ref[h], k, _NT, preferred_element_type=jnp.float32)
            if edge:
                s = jnp.where(visible, s, MASK_VALUE)
            m_prev = m_scr[h]
            m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_scr[h] = alpha * l_scr[h] + p.sum(axis=1, keepdims=True)
            m_scr[h] = m_cur
            acc_scr[h] = alpha * acc_scr[h] + jax.lax.dot_general(
                p.astype(v.dtype), v[:, h * v_dim:(h + 1) * v_dim], _NN,
                preferred_element_type=jnp.float32,
            )

    # a block is on the causal edge where it holds a key past the chunk's first query
    edge = k_lo + block_k - 1 > q_start
    pl.when(jnp.logical_and(kb <= last, ~edge))(functools.partial(visit, False))
    pl.when(jnp.logical_and(kb <= last, edge))(functools.partial(visit, True))

    @pl.when(kb == last)
    def _done():
        for h in range(hg):
            o_ref[:, h * v_dim:(h + 1) * v_dim] = (acc_scr[h] / l_scr[h]).astype(o_ref.dtype)


def mla_chunk_attention_kernel(
    q_start: jax.Array,  # [B] int32
    q: jax.Array,  # [B, H, c, nope+rope]  scaled
    c_kv: jax.Array,  # [B, T, r]
    k_rope: jax.Array,  # [B, T, rope]
    w_uk: jax.Array,  # [r, H*nope]
    w_uv: jax.Array,  # [r, H*v]
    *,
    block_k: int,
    interpret: bool = False,
) -> jax.Array:
    B, H, c, qk = q.shape
    T, r = c_kv.shape[1], c_kv.shape[2]
    rope = k_rope.shape[-1]
    nope, v_dim = w_uk.shape[1] // H, w_uv.shape[1] // H
    hg = math.gcd(H, HEADS_PER_STEP)

    def kv_block(b, g, kb, qs):  # clamped to the last live block: no dead block is copied
        return (b, jnp.minimum(kb, (qs[b] + c - 1) // block_k), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // hg, T // block_k),
        in_specs=[
            pl.BlockSpec((None, hg, c, qk), lambda b, g, kb, qs: (b, g, 0, 0)),
            pl.BlockSpec((None, block_k, r), kv_block),
            pl.BlockSpec((None, block_k, rope), kv_block),
            pl.BlockSpec((r, hg * nope), lambda b, g, kb, qs: (0, g)),
            pl.BlockSpec((r, hg * v_dim), lambda b, g, kb, qs: (0, g)),
        ],
        out_specs=pl.BlockSpec((None, c, hg * v_dim), lambda b, g, kb, qs: (b, 0, g)),
        scratch_shapes=[
            pltpu.VMEM((hg, c, 1), jnp.float32),
            pltpu.VMEM((hg, c, 1), jnp.float32),
            pltpu.VMEM((hg, c, v_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _mla_chunk_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, c, H * v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q_start, q, c_kv, k_rope, w_uk, w_uv)
