"""Config-driven transformer LM: GQA / MLA / local:global, dense or MoE FFN.

Scan-over-layers with stacked per-layer params (compile-once layer body; the
production approach for deep models).  Heterogeneous local:global attention
(gemma3's 5:1 pattern) stays inside one scan body via a per-layer flag.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import LMConfig
from .attention import (
    NEG_INF,
    chunked_gqa_attention,
    decode_attention,
    gqa_attention,
    insert_chunk,
    insert_kv,
    mla_decode,
    mla_prefill,
)
from .layers import PSpec, apply_rope, rms_norm
from .moe import MoEDims, moe_ffn, moe_specs


# --------------------------------------------------------------------------
# param specs
# --------------------------------------------------------------------------
def lm_specs(cfg: LMConfig) -> dict:
    L, d, V = cfg.n_layers, cfg.d_model, cfg.vocab
    hq, hkv, dh, f = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff
    layers: dict[str, Any] = {
        "ln1": PSpec((L, d), ("layers", "embed"), init="zeros"),
        "ln2": PSpec((L, d), ("layers", "embed"), init="zeros"),
    }
    # The 4-D projections below contract their second axis (for wo the two
    # head axes), not the second-to-last one init_params assumes.  Scaled by
    # that wrong fan-in, attention scores reach std ~d_model / heads (128 at
    # DeepSeek-7B's widths): a softmax so peaked that rounding flips which
    # key wins.
    if cfg.mla is not None:
        m = cfg.mla
        e_q = m.nope_head_dim + m.rope_head_dim
        layers["attn"] = {
            "w_dq": PSpec((L, d, m.q_lora_rank), ("layers", "embed", "q_lora")),
            "w_uq": PSpec(
                (L, m.q_lora_rank, hq, e_q), ("layers", "q_lora", "heads", "head_dim"),
                scale=m.q_lora_rank**-0.5,
            ),
            "w_dkv": PSpec(
                (L, d, m.kv_lora_rank + m.rope_head_dim), ("layers", "embed", "kv_lora")
            ),
            "w_uk": PSpec(
                (L, m.kv_lora_rank, hq, m.nope_head_dim),
                ("layers", "kv_lora", "heads", "head_dim"),
                scale=m.kv_lora_rank**-0.5,
            ),
            "w_uv": PSpec(
                (L, m.kv_lora_rank, hq, m.v_head_dim),
                ("layers", "kv_lora", "heads", "head_dim"),
                scale=m.kv_lora_rank**-0.5,
            ),
            "w_o": PSpec(
                (L, hq * m.v_head_dim, d), ("layers", "qkv", "embed")
            ),
        }
    else:
        layers["attn"] = {
            # attn_in/attn_out default to replicated; archs whose head counts
            # don't divide the model axis (gemma3: 8 heads vs 16-way) override
            # them for weight/optimizer STORAGE sharding (weight-gathered)
            "wq": PSpec(
                (L, d, hq, dh), ("layers", "attn_in", "heads", "head_dim"),
                scale=d**-0.5,
            ),
            "wk": PSpec(
                (L, d, hkv, dh), ("layers", "attn_in", "kv_heads", "head_dim"),
                scale=d**-0.5,
            ),
            "wv": PSpec(
                (L, d, hkv, dh), ("layers", "attn_in", "kv_heads", "head_dim"),
                scale=d**-0.5,
            ),
            "wo": PSpec(
                (L, hq, dh, d), ("layers", "heads", "head_dim", "attn_out"),
                scale=(hq * dh) ** -0.5,
            ),
        }
    if cfg.moe is not None:
        layers["moe"] = moe_specs(cfg.moe, d, L)
    else:
        layers["ffn"] = {
            "w_gate": PSpec((L, d, f), ("layers", "embed", "ff")),
            "w_up": PSpec((L, d, f), ("layers", "embed", "ff")),
            "w_down": PSpec((L, f, d), ("layers", "ff", "embed")),
        }
    specs = {
        "embed": PSpec((V, d), ("vocab", "embed"), scale=0.02),
        "layers": layers,
        "final_norm": PSpec((d,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        specs["head"] = PSpec((d, V), ("embed", "vocab"))
    return specs


def layer_flags(cfg: LMConfig) -> np.ndarray:
    """is_global per layer: gemma3 pattern 'n_local local then 1 global'."""
    if cfg.local_global is None:
        return np.ones(cfg.n_layers, dtype=np.float32)
    n_local, n_global = cfg.local_global
    cycle = n_local + n_global
    flags = [(i % cycle) >= n_local for i in range(cfg.n_layers)]
    return np.asarray(flags, dtype=np.float32)


def _moe_dims(cfg: LMConfig) -> MoEDims:
    assert cfg.moe is not None
    return MoEDims(
        n_experts=cfg.moe.n_experts,
        top_k=cfg.moe.top_k,
        n_shared=cfg.moe.n_shared,
        d_model=cfg.d_model,
        d_ff=cfg.moe.d_ff_expert,
        group_size=cfg.moe_group_size,
        capacity_factor=cfg.moe_capacity_factor,
        ep_axis=cfg.moe_ep_axis,
        token_axes=tuple(cfg.moe_token_axes),
    )


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------
def _attn_block(cfg: LMConfig, p: dict, x, positions, is_global, *, causal=True):
    """Full-sequence attention (train / prefill). Returns (out, kv-for-cache)."""
    B, S, _ = x.shape
    if cfg.mla is not None:
        m = cfg.mla
        out, c_kv, k_rope = mla_prefill(
            x,
            p,
            n_heads=cfg.n_heads,
            nope=m.nope_head_dim,
            rope=m.rope_head_dim,
            v_dim=m.v_head_dim,
            positions=positions,
            theta=cfg.rope_theta,
            causal=causal,
            attn_impl=cfg.attention_impl,
            block_q=cfg.attn_block_q,
        )
        out = jnp.einsum("bse,ed->bsd", out, p["w_o"])
        return out, (c_kv, k_rope)
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    window = None if cfg.local_global is None else cfg.local_window
    if cfg.attention_impl == "chunked":
        out = chunked_gqa_attention(
            q, k, v, causal=causal, window=window, global_flag=is_global,
            block_q=cfg.attn_block_q,
        )
    else:
        out = gqa_attention(
            q, k, v, causal=causal, window=window, global_flag=is_global
        )
    out = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return out, (k, v)


def _ffn_block(cfg: LMConfig, layer_p: dict, x):
    if cfg.moe is not None:
        return moe_ffn(x, layer_p["moe"], _moe_dims(cfg))
    f = layer_p["ffn"]
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, f["w_gate"]))
    u = jnp.einsum("bsd,df->bsf", x, f["w_up"])
    return jnp.einsum("bsf,fd->bsd", g * u, f["w_down"])


def _layer(cfg: LMConfig, layer_p: dict, x, positions, is_global, *, collect_kv=False):
    h, kv = _attn_block(
        cfg, layer_p["attn"], rms_norm(x, layer_p["ln1"]), positions, is_global
    )
    x = x + h
    x = x + _ffn_block(cfg, layer_p, rms_norm(x, layer_p["ln2"]))
    return x, (kv if collect_kv else None)


# --------------------------------------------------------------------------
# model entry points
# --------------------------------------------------------------------------
def forward(
    params: dict,
    cfg: LMConfig,
    tokens: jax.Array,
    *,
    remat: Any = None,
    unroll: int = 1,
    collect_kv: bool = False,
    last_only: bool = False,
    no_head: bool = False,
):
    """tokens [B,S] -> (logits [B,S,V] fp32, cache pytree | None).
    With no_head=True returns the final hidden states instead of logits."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    flags = jnp.asarray(layer_flags(cfg))

    def _carry_constraint(y):
        if not (cfg.act_batch_axes or cfg.act_seq_axes):
            return y
        from jax.sharding import PartitionSpec as P

        spec = P(
            tuple(cfg.act_batch_axes) or None,
            tuple(cfg.act_seq_axes) or None,
            None,
        )
        return jax.lax.with_sharding_constraint(y, spec)

    def body(carry, xs):
        layer_p, is_global = xs
        y, kv = _layer(cfg, layer_p, carry, positions, is_global, collect_kv=collect_kv)
        return _carry_constraint(y), kv

    if remat is not None:
        body = jax.checkpoint(body, policy=remat)
    x, caches = jax.lax.scan(body, x, (params["layers"], flags), unroll=unroll)
    x = rms_norm(x, params["final_norm"])
    if last_only:
        x = x[:, -1:]  # vLLM-style: prefill only needs the last position
    if no_head:
        return x, caches
    head = params.get("head")
    if head is None:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head)
    return logits.astype(jnp.float32), caches


def streaming_ce_loss(
    x: jax.Array,  # [B,S,d] final hidden (normed)
    head: jax.Array,  # [d,V] (or transposed embed for tied)
    targets: jax.Array,  # [B,S]
    n_chunks: int,
) -> jax.Array:
    """CE via running logsumexp over vocab chunks: the fp32 [B,S,V] logits
    tensor never materializes (peak extra memory = one [B,S,V/n] chunk)."""
    V = head.shape[-1]
    assert V % n_chunks == 0, (V, n_chunks)
    c = V // n_chunks

    def body(carry, i):
        m_prev, s_prev, tgt_prev = carry
        h = jax.lax.dynamic_slice_in_dim(head, i * c, c, axis=1)
        lg = jnp.einsum("bsd,dv->bsv", x, h).astype(jnp.float32)
        m_cur = jnp.maximum(m_prev, lg.max(-1))
        s_cur = s_prev * jnp.exp(m_prev - m_cur) + jnp.exp(
            lg - m_cur[..., None]
        ).sum(-1)
        mine = (targets >= i * c) & (targets < (i + 1) * c)
        idx = jnp.clip(targets - i * c, 0, c - 1)
        tgt_lg = jnp.take_along_axis(lg, idx[..., None], axis=-1)[..., 0]
        tgt_cur = jnp.where(mine, tgt_lg, tgt_prev)
        return (m_cur, s_cur, tgt_cur), None

    B, S, _ = x.shape
    init = (
        jnp.full((B, S), -jnp.inf, jnp.float32),
        jnp.zeros((B, S), jnp.float32),
        jnp.zeros((B, S), jnp.float32),
    )
    (m, s, tgt), _ = jax.lax.scan(
        jax.checkpoint(body), init, jnp.arange(n_chunks)
    )
    return (jnp.log(s) + m - tgt).mean()


def prefill(params: dict, cfg: LMConfig, tokens: jax.Array, *, unroll: int = 1):
    """Returns (last-position logits [B,V], cache dict, cache_len [B])."""
    B, S = tokens.shape
    logits, caches = forward(
        params, cfg, tokens, unroll=unroll, collect_kv=True,
        last_only=cfg.prefill_last_only,
    )
    if cfg.mla is not None:
        cache = {"c_kv": caches[0], "k_rope": caches[1]}
    else:
        cache = {"k": caches[0], "v": caches[1]}
    cache_len = jnp.full((B,), S, jnp.int32)
    return logits[:, -1], cache, cache_len


def decode_step(
    params: dict,
    cfg: LMConfig,
    tokens: jax.Array,  # [B,1]
    cache: dict,  # stacked over layers: [L,B,T,...]
    cache_len: jax.Array,  # [B] current valid length (new token goes here)
    *,
    unroll: int = 1,
):
    """One decode step. Returns (logits [B,V], new_cache, new_cache_len)."""
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    positions = cache_len[:, None]  # [B,1]
    flags = jnp.asarray(layer_flags(cfg))
    window = None if cfg.local_global is None else cfg.local_window

    if cfg.mla is not None:
        m = cfg.mla

        def body(carry, xs):
            layer_p, c_kv_c, k_rope_c, is_global = xs
            h = rms_norm(carry, layer_p["ln1"])
            ckv_full = jnp.einsum("bsd,dr->bsr", h, layer_p["attn"]["w_dkv"])
            new_ckv, new_krope = (
                ckv_full[..., : -m.rope_head_dim],
                ckv_full[..., -m.rope_head_dim :],
            )
            new_krope = apply_rope(new_krope[:, :, None, :], positions, cfg.rope_theta)[
                :, :, 0, :
            ]
            c_kv_c = insert_kv(c_kv_c, new_ckv, cache_len)
            k_rope_c = insert_kv(k_rope_c, new_krope, cache_len)
            out = mla_decode(
                h,
                layer_p["attn"],
                c_kv_c,
                k_rope_c,
                cache_len,
                n_heads=cfg.n_heads,
                nope=m.nope_head_dim,
                rope=m.rope_head_dim,
                v_dim=m.v_head_dim,
                positions=positions,
                theta=cfg.rope_theta,
            )
            out = jnp.einsum("bse,ed->bsd", out, layer_p["attn"]["w_o"])
            y = carry + out
            y = y + _ffn_block(cfg, layer_p, rms_norm(y, layer_p["ln2"]))
            return y, (c_kv_c, k_rope_c)

        x, (c_kv_new, k_rope_new) = jax.lax.scan(
            body,
            x,
            (params["layers"], cache["c_kv"], cache["k_rope"], flags),
            unroll=unroll,
        )
        new_cache = {"c_kv": c_kv_new, "k_rope": k_rope_new}
    else:

        def body(carry, xs):
            layer_p, k_c, v_c, is_global = xs
            ap = layer_p["attn"]
            h = rms_norm(carry, layer_p["ln1"])
            q = jnp.einsum("bsd,dhe->bshe", h, ap["wq"])
            k = jnp.einsum("bsd,dhe->bshe", h, ap["wk"])
            v = jnp.einsum("bsd,dhe->bshe", h, ap["wv"])
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            k_c = insert_kv(k_c, k, cache_len)
            v_c = insert_kv(v_c, v, cache_len)
            out = decode_attention(
                q, k_c, v_c, cache_len, window=window, global_flag=is_global
            )
            out = jnp.einsum("bshe,hed->bsd", out, ap["wo"])
            y = carry + out
            y = y + _ffn_block(cfg, layer_p, rms_norm(y, layer_p["ln2"]))
            return y, (k_c, v_c)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], flags), unroll=unroll
        )
        new_cache = {"k": k_new, "v": v_new}

    x = rms_norm(x, params["final_norm"])
    head = params.get("head")
    if head is None:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head)
    return logits[:, 0].astype(jnp.float32), new_cache, cache_len + 1


def init_cache(cfg: LMConfig, batch: int, max_len: int) -> dict:
    """Zeroed KV (or MLA latent) cache stacked over layers."""
    L = cfg.n_layers
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c_kv": jnp.zeros((L, batch, max_len, m.kv_lora_rank), cfg.dtype),
            "k_rope": jnp.zeros((L, batch, max_len, m.rope_head_dim), cfg.dtype),
        }
    return {
        "k": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cfg.dtype),
        "v": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cfg.dtype),
    }


def prefill_chunk(
    params: dict,
    cfg: LMConfig,
    tokens: jax.Array,  # [B,c]
    cache: dict,  # [L,B,T,...]
    cache_len: jax.Array,  # [B] valid length before this chunk
    *,
    unroll: int = 1,
):
    """Chunked prefill against an existing cache (serving engine / RISP
    prefix reuse): appends c tokens at positions cache_len..cache_len+c-1.
    Returns (last-position logits [B,V], new_cache, new_cache_len)."""
    B, c = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    positions = cache_len[:, None] + jnp.arange(c)[None, :]
    flags = jnp.asarray(layer_flags(cfg))
    window = None if cfg.local_global is None else cfg.local_window

    if cfg.mla is not None:
        m = cfg.mla

        def body(carry, xs):
            layer_p, c_kv_c, k_rope_c, is_global = xs
            h = rms_norm(carry, layer_p["ln1"])
            ckv_full = jnp.einsum("bsd,dr->bsr", h, layer_p["attn"]["w_dkv"])
            new_ckv = ckv_full[..., : -m.rope_head_dim]
            new_krope = apply_rope(
                ckv_full[..., -m.rope_head_dim :][:, :, None, :], positions,
                cfg.rope_theta,
            )[:, :, 0, :]
            c_kv_c = insert_chunk(c_kv_c, new_ckv, cache_len)
            k_rope_c = insert_chunk(k_rope_c, new_krope, cache_len)
            out = mla_decode(
                h, layer_p["attn"], c_kv_c, k_rope_c, cache_len,
                n_heads=cfg.n_heads, nope=m.nope_head_dim, rope=m.rope_head_dim,
                v_dim=m.v_head_dim, positions=positions, theta=cfg.rope_theta,
            )
            out = jnp.einsum("bse,ed->bsd", out, layer_p["attn"]["w_o"])
            y = carry + out
            y = y + _ffn_block(cfg, layer_p, rms_norm(y, layer_p["ln2"]))
            return y, (c_kv_c, k_rope_c)

        x, (ckv_new, krope_new) = jax.lax.scan(
            body, x, (params["layers"], cache["c_kv"], cache["k_rope"], flags),
            unroll=unroll,
        )
        new_cache = {"c_kv": ckv_new, "k_rope": krope_new}
    else:

        def body(carry, xs):
            layer_p, k_c, v_c, is_global = xs
            ap = layer_p["attn"]
            h = rms_norm(carry, layer_p["ln1"])
            q = jnp.einsum("bsd,dhe->bshe", h, ap["wq"])
            k = jnp.einsum("bsd,dhe->bshe", h, ap["wk"])
            v = jnp.einsum("bsd,dhe->bshe", h, ap["wv"])
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            k_c = insert_chunk(k_c, k, cache_len)
            v_c = insert_chunk(v_c, v, cache_len)
            out = decode_attention(
                q, k_c, v_c, cache_len, window=window, global_flag=is_global
            )
            out = jnp.einsum("bshe,hed->bsd", out, ap["wo"])
            y = carry + out
            y = y + _ffn_block(cfg, layer_p, rms_norm(y, layer_p["ln2"]))
            return y, (k_c, v_c)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], flags), unroll=unroll
        )
        new_cache = {"k": k_new, "v": v_new}

    x = rms_norm(x, params["final_norm"])
    head = params.get("head")
    if head is None:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head)
    return logits[:, -1].astype(jnp.float32), new_cache, cache_len + c
