"""Attention variants: GQA full/causal/local, MLA, cached steps.

A cached step's new rows reach the cache through one writer, ``put_rows``
(a dynamic-update-slice per batch row, overwriting).  Latent attention
writes a layer's rows before it attends them; GQA attends the cache as it
was plus the step's own rows (``decode_attention(new=...)``), and the step
writes every layer's rows once, after its layer scan, so the scan copies
no layer's cache.

These are pure jnp, with one exception on a served path: a prefill chunk's
cached latent attention (``mla_chunk_attend``, DeepSeek-V2 through
``ServeEngine``) lowers to the Pallas kernel
``repro.kernels.mla_chunk_attention`` when it is lowered for a TPU, and to
its XLA form for any other platform (``jax.lax.platform_dependent``; no
option chooses).  The other kernels in ``repro.kernels`` are on no served
path; the jnp forms here are their oracles.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..kernels.mla_chunk_attention import mla_chunk_attention
from .layers import apply_rope_interleaved, rms_norm, yarn_mscale

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: [B,S,Hkv,G,dh]  k: [B,T,Hkv,dh] -> scores [B,Hkv,G,S,T]."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k)


def gqa_attention(
    q: jax.Array,  # [B,S,Hq,dh]
    k: jax.Array,  # [B,T,Hkv,dh]
    v: jax.Array,  # [B,T,Hkv,dh]
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    global_flag: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """``window`` restricts attention to a sliding window; a traced
    ``global_flag`` (0.0/1.0 per layer, e.g. gemma3's 5:1 pattern) disables
    the window when 1 so local and global layers share one scan body."""
    B, S, Hq, dh = q.shape
    _, T, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, dh)
    scores = _gqa_scores(qg, k).astype(jnp.float32)
    if scale is None:
        scores = scores / jnp.sqrt(dh).astype(jnp.float32)
    else:
        scores = scores * scale

    qpos = jnp.arange(S) + q_offset
    kpos = jnp.arange(T)
    mask = jnp.ones((S, T), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        in_window = kpos[None, :] > qpos[:, None] - window
        if global_flag is not None:
            in_window = in_window | (global_flag > 0.5)
        mask &= in_window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, Hq, v.shape[-1])  # v_dim may differ from q (MLA)


def decode_attention(
    q: jax.Array,  # [B,S,Hq,dh]  (S=1 decode; S=chunk for chunked prefill)
    k_cache: jax.Array,  # [B,T,Hkv,dh]
    v_cache: jax.Array,
    q_start: jax.Array,  # [B] position of the FIRST query token
    *,
    window: int | None = None,
    global_flag: jax.Array | None = None,
    new: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Cached attention: query token s attends kpos <= q_start+s.

    Without ``new`` the queries' own keys are already in the cache.  With
    ``new`` = (k, v) [B,S,Hkv,dh], the queries' own keys, not yet written:
    the cache's rows from q_start on are ignored, and query s attends the
    rows before q_start and new rows 0..s.  The cache is then only read, so
    a step can write its rows once, after its last layer."""
    B, S, Hq, dh = q.shape
    _, T, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_cache)
    kpos = jnp.broadcast_to(jnp.arange(T), (B, T))
    if new is not None:
        live = jnp.where(kpos < q_start[:, None], kpos, jnp.iinfo(jnp.int32).max)
        kpos = jnp.concatenate([live, q_start[:, None] + jnp.arange(S)], axis=1)
        own = jnp.einsum("bskgd,btkd->bkgst", qg, new[0].astype(k_cache.dtype))
        scores = jnp.concatenate([scores, own], axis=-1)
    scores = scores.astype(jnp.float32) / jnp.sqrt(dh).astype(jnp.float32)
    qpos = q_start[:, None] + jnp.arange(S)[None, :]  # [B,S]
    mask = kpos[:, None, :] <= qpos[:, :, None]  # [B,S,keys]
    if window is not None:
        in_window = kpos[:, None, :] > qpos[:, :, None] - window
        if global_flag is not None:
            in_window = in_window | (global_flag > 0.5)
        mask &= in_window
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    pv = partial(jnp.einsum, "bkgst,btkd->bskgd", preferred_element_type=jnp.float32)
    out = pv(probs[..., :T], v_cache)
    if new is not None:
        out = out + pv(probs[..., T:], new[1].astype(v_cache.dtype))
    return out.astype(q.dtype).reshape(B, S, Hq, v_cache.shape[-1])


def chunked_gqa_attention(
    q: jax.Array,  # [B,S,Hq,dh]
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    global_flag: jax.Array | None = None,
    block_q: int = 256,
    scale: float | None = None,
) -> jax.Array:
    """Q-chunked attention with per-chunk rematerialization: the S x T score
    matrix never materializes beyond one (block_q x T) tile, and backward
    recomputes per chunk — the XLA-level analogue of the Pallas flash kernel
    (which replaces this on real TPU).  Peak score memory drops S/block_q x.
    """
    B, S, Hq, dh = q.shape
    if S <= block_q:
        return gqa_attention(
            q, k, v, causal=causal, window=window, global_flag=global_flag,
            scale=scale,
        )
    pad = (-S) % block_q
    qp = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)]) if pad else q
    nb = qp.shape[1] // block_q
    qb = jnp.moveaxis(qp.reshape(B, nb, block_q, Hq, dh), 1, 0)  # [nb,B,blk,H,dh]
    offsets = jnp.arange(nb) * block_q

    def body(_, xs):
        q_chunk, off = xs
        out = gqa_attention(
            q_chunk,
            k,
            v,
            causal=causal,
            window=window,
            q_offset=off,
            global_flag=global_flag,
            scale=scale,
        )
        return None, out

    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qb, offsets))
    v_dim = outs.shape[-1]  # may differ from q's head dim (MLA)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nb * block_q, Hq, v_dim)
    return out[:, :S]


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2, arXiv:2405.04434)
#
#   c_q = q_a_norm(x W_dq);  [q_nope, q_rope] = c_q W_uq
#   [c_kv, k_rope] = x W_dkv;  c_kv = kv_a_norm(c_kv)
#   k_nope = c_kv W_uk;  v = c_kv W_uv;  k = [k_nope, k_rope] (k_rope shared)
#   rope (YaRN where configured) on q_rope and k_rope, DeepSeek's interleaved
#   pairs; softmax scale (nope+rope)^-1/2 * mscale(factor, mscale_all_dim)^2.
#
# The cache holds the normed latent c_kv and the roped k_rope.  Three ways to
# attend: the full decompressed form (forward), the absorbed form against
# the cache (decode: W_uk folded into the query, W_uv applied after), and the
# decompressed form blocked over the live keys with an online softmax
# (prefill chunks: no [heads, chunk, max_len] score tensor).
# --------------------------------------------------------------------------
def mla_softmax_scale(qk_dim: int, yarn=None) -> float:
    scale = qk_dim**-0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def mla_project(x, p, *, rope: int, positions, theta: float, yarn=None):
    """x [B,S,d] -> (q [B,S,H,nope+rope] roped, c_kv [B,S,r] normed,
    k_rope [B,S,rope] roped)."""
    nope = p["w_uq"].shape[-1] - rope
    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dq"]), p["q_norm"])
    q = jnp.einsum("bsr,rhe->bshe", cq, p["w_uq"])
    q_rope = apply_rope_interleaved(q[..., nope:], positions, theta, yarn)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_kv = rms_norm(ckv_full[..., :-rope], p["kv_norm"])
    k_rope = apply_rope_interleaved(
        ckv_full[..., -rope:][:, :, None, :], positions, theta, yarn
    )[:, :, 0, :]
    return q, c_kv, k_rope


def mla_prefill(
    x: jax.Array,  # [B,S,d]
    p: dict,
    *,
    n_heads: int,
    nope: int,
    rope: int,
    v_dim: int,
    positions: jax.Array,
    theta: float,
    yarn=None,
    causal: bool = True,
    attn_impl: str = "einsum",
    block_q: int = 512,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (attn_out [B,S,H*v_dim], c_kv [B,S,r], k_rope [B,S,rope])."""
    B, S, _ = x.shape
    qf, c_kv, k_rope = mla_project(x, p, rope=rope, positions=positions, theta=theta, yarn=yarn)
    scale = mla_softmax_scale(nope + rope, yarn)
    with jax.named_scope("mla.attend"):
        k_nope = jnp.einsum("bsr,rhe->bshe", c_kv, p["w_uk"])  # [B,S,H,nope]
        v = jnp.einsum("bsr,rhe->bshe", c_kv, p["w_uv"])  # [B,S,H,v_dim]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, n_heads, rope))],
            axis=-1,
        )
        if attn_impl == "chunked":
            out = chunked_gqa_attention(qf, k, v, causal=causal, block_q=block_q, scale=scale)
        else:
            out = gqa_attention(qf, k, v, causal=causal, scale=scale)
    return out.reshape(B, S, n_heads * v_dim), c_kv, k_rope


def mla_decode(
    q: jax.Array,  # [B,S,H,nope+rope], roped (S=1 decode)
    p: dict,
    c_kv_cache: jax.Array,  # [B,T,r]   (new latents already inserted)
    k_rope_cache: jax.Array,  # [B,T,rope]
    q_start: jax.Array,  # [B] position of the FIRST query token
    *,
    scale: float,
) -> jax.Array:
    """Absorbed-matmul cached attention: scores live in the latent space, the
    cache is only the rank-r latent + shared rope key — the MLA memory win.
    Query token s (global position q_start+s) attends kpos <= q_start+s.
    Returns [B,S,H*v_dim]."""
    B, S, H, _ = q.shape
    T = c_kv_cache.shape[1]
    nope = p["w_uk"].shape[-1]
    with jax.named_scope("mla.attend"):
        # absorb w_uk into the query: q_lat [B,S,H,r]
        q_lat = jnp.einsum("bshe,rhe->bshr", q[..., :nope], p["w_uk"])
        scores = jnp.einsum("bshr,btr->bhst", q_lat, c_kv_cache)
        scores = scores + jnp.einsum("bshe,bte->bhst", q[..., nope:], k_rope_cache)
        scores = scores.astype(jnp.float32) * scale

        kpos = jnp.arange(T)
        qpos = q_start[:, None] + jnp.arange(S)[None, :]  # [B,S]
        mask = kpos[None, None, :] <= qpos[:, :, None]  # [B,S,T]
        scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)

        ctx_lat = jnp.einsum("bhst,btr->bshr", probs, c_kv_cache)  # [B,S,H,r]
        v = jnp.einsum("bshr,rhe->bshe", ctx_lat, p["w_uv"])  # [B,S,H,v_dim]
    return v.reshape(B, S, H * v.shape[-1])


def mla_chunk_attend(
    q: jax.Array,  # [B,c,H,nope+rope], roped
    p: dict,
    c_kv_cache: jax.Array,  # [B,T,r]   (this chunk's latents already inserted)
    k_rope_cache: jax.Array,  # [B,T,rope]
    q_start: jax.Array,  # [B] position of the chunk's first token
    *,
    scale: float,
    block: int = 512,
) -> jax.Array:
    """Decompressed cached attention for a prefill chunk: query s attends
    keys 0..q_start+s.  Lowered for a TPU it is one Pallas kernel
    (``repro.kernels.mla_chunk_attention``: score tiles stay in VMEM); for
    any other platform it is ``_mla_chunk_blocks``, over key blocks of
    ``block`` (the kernel keeps its own tiling, so ``block`` applies only
    off the TPU).  Returns [B,c,H*v_dim]."""
    with jax.named_scope("mla.attend"):
        return jax.lax.platform_dependent(
            q, p["w_uk"], p["w_uv"], c_kv_cache, k_rope_cache, q_start,
            tpu=partial(mla_chunk_attention, scale=scale),
            default=partial(_mla_chunk_blocks, scale=scale, block=block),
        )


def _mla_chunk_blocks(q, w_uk, w_uv, c_kv_cache, k_rope_cache, q_start, *,
                      scale: float, block: int) -> jax.Array:
    """``mla_chunk_attend`` in XLA, over key blocks of ``block`` with an
    online softmax.  Only the blocks up to the live length (q_start + c) are
    visited, so the work and the peak memory (one float32 [B,H,c,block]
    score tile) do not grow with the cache's ``max_len``."""
    B, c, H, _ = q.shape
    T = c_kv_cache.shape[1]
    nope = w_uk.shape[-1]
    v_dim = w_uv.shape[-1]
    blk = block if T % block == 0 else T
    qpos = q_start[:, None] + jnp.arange(c)[None, :]  # [B,c]
    n_blocks = (jnp.max(q_start) + c + blk - 1) // blk

    def body(i, carry):
        m_prev, l_prev, acc = carry
        ckv = jax.lax.dynamic_slice_in_dim(c_kv_cache, i * blk, blk, axis=1)
        krope = jax.lax.dynamic_slice_in_dim(k_rope_cache, i * blk, blk, axis=1)
        k_nope = jnp.einsum("btr,rhe->bthe", ckv, w_uk)
        v = jnp.einsum("btr,rhe->bthe", ckv, w_uv)
        s = jnp.einsum("bshe,bthe->bhst", q[..., :nope], k_nope)
        s = s + jnp.einsum("bshe,bte->bhst", q[..., nope:], krope)
        s = s.astype(jnp.float32) * scale
        kpos = i * blk + jnp.arange(blk)
        mask = kpos[None, None, :] <= qpos[:, :, None]  # [B,c,blk]
        s = jnp.where(mask[:, None], s, NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(-1))
        pr = jnp.exp(s - m_cur[..., None])
        corr = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * corr + pr.sum(-1)
        pv = jnp.einsum("bhst,bthe->bshe", pr.astype(q.dtype), v).astype(jnp.float32)
        acc = acc * jnp.moveaxis(corr, 1, 2)[..., None] + pv
        return m_cur, l_cur, acc

    init = (
        jnp.full((B, H, c), NEG_INF, jnp.float32),
        jnp.zeros((B, H, c), jnp.float32),
        jnp.zeros((B, c, H, v_dim), jnp.float32),
    )
    _, l_fin, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    out = acc / jnp.moveaxis(l_fin, 1, 2)[..., None]
    return out.astype(q.dtype).reshape(B, c, H * v_dim)


def put_rows(cache: jax.Array, new: jax.Array, cache_len: jax.Array) -> jax.Array:
    """Write new [B,c,...] into cache [B,T,...] at rows cache_len..+c-1,
    overwriting what was there.  A start past T - c is clamped to T - c, so
    callers keep cache_len + c <= T (``ServeEngine.generate`` refuses a
    request that would not)."""
    return jax.vmap(
        lambda a, n, i: jax.lax.dynamic_update_slice_in_dim(a, n, i, axis=0)
    )(cache, new.astype(cache.dtype), cache_len)

