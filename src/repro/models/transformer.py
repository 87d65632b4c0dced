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
    mla_chunk_attend,
    mla_decode,
    mla_prefill,
    mla_project,
    mla_softmax_scale,
    put_rows,
)
from .layers import PSpec, apply_rope, rms_norm
from .moe import EXPERT_WEIGHTS, MoEDims, moe_ffn, moe_ffn_dropless, moe_specs


# --------------------------------------------------------------------------
# param specs
# --------------------------------------------------------------------------
def n_lead(cfg: LMConfig) -> int:
    """Leading dense layers before the MoE layers (DeepSeek's
    ``first_k_dense_replace``); they live in ``params["lead"]`` and
    ``cache["lead"]``, stacked apart from the MoE layers' scan."""
    return cfg.first_k_dense if cfg.moe is not None else 0


def lm_specs(cfg: LMConfig) -> dict:
    k = n_lead(cfg)
    specs = {
        "embed": PSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "layers": _layer_specs(cfg, cfg.n_layers - k, moe=cfg.moe is not None),
        "final_norm": PSpec((cfg.d_model,), ("embed",), init="zeros"),
    }
    if k:
        specs["lead"] = _layer_specs(cfg, k, moe=False)
    if not cfg.tie_embeddings:
        specs["head"] = PSpec((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    return specs


def _layer_specs(cfg: LMConfig, L: int, *, moe: bool) -> dict:
    d = cfg.d_model
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
            "q_norm": PSpec((L, m.q_lora_rank), ("layers", "q_lora"), init="zeros"),
            "w_uq": PSpec(
                (L, m.q_lora_rank, hq, e_q), ("layers", "q_lora", "heads", "head_dim"),
                scale=m.q_lora_rank**-0.5,
            ),
            "w_dkv": PSpec(
                (L, d, m.kv_lora_rank + m.rope_head_dim), ("layers", "embed", "kv_lora")
            ),
            "kv_norm": PSpec((L, m.kv_lora_rank), ("layers", "kv_lora"), init="zeros"),
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
    if moe:
        layers["moe"] = moe_specs(cfg.moe, d, L)
    else:
        layers["ffn"] = {
            "w_gate": PSpec((L, d, f), ("layers", "embed", "ff")),
            "w_up": PSpec((L, d, f), ("layers", "embed", "ff")),
            "w_down": PSpec((L, f, d), ("layers", "ff", "embed")),
        }
    return layers


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
    m = cfg.moe
    return MoEDims(
        n_experts=m.n_experts,
        top_k=m.top_k,
        n_shared=m.n_shared,
        d_model=cfg.d_model,
        d_ff=m.d_ff_expert,
        group_size=cfg.moe_group_size,
        capacity_factor=cfg.moe_capacity_factor,
        ep_axis=cfg.moe_ep_axis,
        token_axes=tuple(cfg.moe_token_axes),
        n_group=m.n_group,
        topk_group=m.topk_group,
        norm_topk=m.norm_topk,
        routed_scale=m.routed_scale,
        held_first=m.held_first,
        n_held=m.n_held,
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
            yarn=cfg.rope_scaling,
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


def _ffn_block(cfg: LMConfig, layer_p: dict, x, dispatch: str = "dropless"):
    """Returns (out, hits): hits [held + 1] int32 counts a dropless MoE
    layer's assignments per held expert (last: held elsewhere), else None.
    ``dispatch`` picks the MoE layer's: "dropless" or "capacity" (GShard)."""
    if "moe" in layer_p:
        if dispatch == "capacity":
            return moe_ffn(x, layer_p["moe"], _moe_dims(cfg)), None
        return moe_ffn_dropless(x, layer_p["moe"], _moe_dims(cfg))
    f = layer_p["ffn"]
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, f["w_gate"]))
    u = jnp.einsum("bsd,df->bsf", x, f["w_up"])
    return jnp.einsum("bsf,fd->bsd", g * u, f["w_down"]), None


def _layer(cfg: LMConfig, layer_p: dict, x, positions, is_global, *, collect_kv=False,
           moe_dispatch="dropless"):
    h, kv = _attn_block(
        cfg, layer_p["attn"], rms_norm(x, layer_p["ln1"]), positions, is_global
    )
    x = x + h
    x = x + _ffn_block(cfg, layer_p, rms_norm(x, layer_p["ln2"]), moe_dispatch)[0]
    return x, (kv if collect_kv else None)


def _stacks(cfg: LMConfig, params: dict, cache: dict | None = None) -> list:
    """(layer params, cache, per-layer flags) of the leading dense layers,
    if any, then of the main stack."""
    k = n_lead(cfg)
    flags = layer_flags(cfg)
    main = None if cache is None else {n: c for n, c in cache.items() if n != "lead"}
    out = [(params["layers"], main, flags[k:])]
    if k:
        out.insert(0, (params["lead"], None if cache is None else cache["lead"], flags[:k]))
    return out


def _scan_steps(cfg: LMConfig, params: dict, cache: dict, body, x, unroll: int):
    """Scan a cached step's ``body`` over every stack; returns (x, new cache,
    the main stack's route counts or None)."""
    new_cache, hits = {}, None
    for layer_p, layer_cache, flags in _stacks(cfg, params, cache):
        step, xs = body, (layer_p, layer_cache, jnp.asarray(flags))
        if "moe" in layer_p:
            # the held experts' weights stay whole, indexed by layer inside
            # (``moe.expert_weights``); the scan slices the rest
            moe = layer_p["moe"]
            whole = {k: moe[k] for k in EXPERT_WEIGHTS}
            rest = {k: v for k, v in moe.items() if k not in whole}
            xs = ({**layer_p, "moe": rest}, *xs[1:], jnp.arange(len(flags)))

            def step(carry, xs, _whole=whole):
                lp, lc, is_global, i = xs
                return body(carry, ({**lp, "moe": {**lp["moe"], **_whole, "layer": i}},
                                    lc, is_global))

        x, (c, hits) = jax.lax.scan(step, x, xs, unroll=unroll)
        if layer_p is params["layers"]:
            new_cache.update(c)
        else:
            new_cache["lead"] = c
    return x, new_cache, hits


def _mla_step(cfg: LMConfig, layer_p: dict, x, lc: dict, cache_len, positions, attend,
              dispatch: str = "dropless"):
    """One MLA layer of a cached step: project, write this step's latents
    into the layer's cache, attend with ``attend``, then the FFN."""
    m = cfg.mla
    ap = layer_p["attn"]
    q, new_ckv, new_krope = mla_project(
        rms_norm(x, layer_p["ln1"]), ap, rope=m.rope_head_dim, positions=positions,
        theta=cfg.rope_theta, yarn=cfg.rope_scaling,
    )
    c_kv_c = put_rows(lc["c_kv"], new_ckv, cache_len)
    k_rope_c = put_rows(lc["k_rope"], new_krope, cache_len)
    scale = mla_softmax_scale(m.nope_head_dim + m.rope_head_dim, cfg.rope_scaling)
    out = attend(q, ap, c_kv_c, k_rope_c, cache_len, scale=scale)
    y = x + jnp.einsum("bse,ed->bsd", out, ap["w_o"])
    f, hits = _ffn_block(cfg, layer_p, rms_norm(y, layer_p["ln2"]), dispatch)
    return y + f, ({"c_kv": c_kv_c, "k_rope": k_rope_c}, hits)


def _logits(params: dict, x) -> jax.Array:
    head = params.get("head")
    if head is None:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x, head)


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
    moe_dispatch: str = "dropless",
):
    """tokens [B,S] -> (logits [B,S,V] fp32, cache pytree | None).
    With no_head=True returns the final hidden states instead of logits.
    MoE layers take the dropless dispatch unless ``moe_dispatch`` is
    "capacity" (the GShard dispatch of the training step and the dry-run)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

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
        y, kv = _layer(cfg, layer_p, carry, positions, is_global, collect_kv=collect_kv,
                       moe_dispatch=moe_dispatch)
        return _carry_constraint(y), kv

    if remat is not None:
        body = jax.checkpoint(body, policy=remat)
    caches = []
    for layer_p, _, flags in _stacks(cfg, params):
        x, kv = jax.lax.scan(body, x, (layer_p, jnp.asarray(flags)), unroll=unroll)
        caches.append(kv)
    if len(caches) == 1:
        caches = caches[0]
    x = rms_norm(x, params["final_norm"])
    if last_only:
        x = x[:, -1:]  # vLLM-style: prefill only needs the last position
    if no_head:
        return x, caches
    return _logits(params, x).astype(jnp.float32), caches


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


def prefill(params: dict, cfg: LMConfig, tokens: jax.Array, *, unroll: int = 1,
            moe_dispatch: str = "dropless"):
    """Returns (last-position logits [B,V], cache dict, cache_len [B])."""
    B, S = tokens.shape
    logits, caches = forward(
        params, cfg, tokens, unroll=unroll, collect_kv=True,
        last_only=cfg.prefill_last_only, moe_dispatch=moe_dispatch,
    )
    names = ("c_kv", "k_rope") if cfg.mla is not None else ("k", "v")
    if n_lead(cfg):
        lead, caches = caches
        cache = {**dict(zip(names, caches)), "lead": dict(zip(names, lead))}
    else:
        cache = dict(zip(names, caches))
    cache_len = jnp.full((B,), S, jnp.int32)
    return logits[:, -1], cache, cache_len


def init_cache(cfg: LMConfig, batch: int, max_len: int) -> dict:
    """Zeroed KV (or MLA latent) cache stacked over layers; the leading dense
    layers' under ``"lead"``."""

    def zeros(L):
        if cfg.mla is not None:
            m = cfg.mla
            return {
                "c_kv": jnp.zeros((L, batch, max_len, m.kv_lora_rank), cfg.dtype),
                "k_rope": jnp.zeros((L, batch, max_len, m.rope_head_dim), cfg.dtype),
            }
        shape = (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}

    k = n_lead(cfg)
    cache = zeros(cfg.n_layers - k)
    if k:
        cache["lead"] = zeros(k)
    return cache


def prefill_chunk(
    params: dict,
    cfg: LMConfig,
    tokens: jax.Array,  # [B,c]
    cache: dict,  # [L,B,T,...]
    cache_len: jax.Array,  # [B] valid length before this chunk
    *,
    unroll: int = 1,
    route_stats: bool = False,
    moe_dispatch: str = "dropless",
):
    """The one cached step, for any width c >= 1 (serving engine / RISP
    prefix reuse): attends each of c tokens to the rows before it and
    writes them at rows cache_len..cache_len+c-1.  Returns (last-position logits [B,V],
    new_cache, cache_len + c), and with ``route_stats`` the MoE layers'
    route counts [L_moe, held + 1] (``moe_ffn_dropless``'s hits; None
    without MoE or with the "capacity" ``moe_dispatch``) after them.

    MLA attends a single token in the absorbed form (``mla_decode``) and a
    wider chunk in the decompressed form, blocked over the live keys
    (``mla_chunk_attend``): a chunk's queries are many, so decompressing its
    keys costs less than absorbing W_uk into every query."""
    B, c = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    positions = cache_len[:, None] + jnp.arange(c)[None, :]
    window = None if cfg.local_global is None else cfg.local_window

    if cfg.mla is not None:
        attend = mla_decode if c == 1 else mla_chunk_attend

        def body(carry, xs):
            layer_p, lc, is_global = xs
            return _mla_step(cfg, layer_p, carry, lc, cache_len, positions, attend,
                             moe_dispatch)

    else:

        def body(carry, xs):
            layer_p, lc, is_global = xs
            ap = layer_p["attn"]
            h = rms_norm(carry, layer_p["ln1"])
            q = jnp.einsum("bsd,dhe->bshe", h, ap["wq"])
            k = jnp.einsum("bsd,dhe->bshe", h, ap["wk"])
            v = jnp.einsum("bsd,dhe->bshe", h, ap["wv"])
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            out = decode_attention(
                q, lc["k"], lc["v"], cache_len, window=window, global_flag=is_global,
                new=(k, v),
            )
            out = jnp.einsum("bshe,hed->bsd", out, ap["wo"])
            y = carry + out
            f, hits = _ffn_block(cfg, layer_p, rms_norm(y, layer_p["ln2"]), moe_dispatch)
            return y + f, ({"k": k, "v": v}, hits)

    x, new_cache, hits = _scan_steps(cfg, params, cache, body, x, unroll)
    if cfg.mla is None:
        # GQA's scan gave each layer's new rows; written here, not inside
        # the scan, no layer's cache is copied out of it and back
        write = jax.vmap(put_rows, in_axes=(0, 0, None))
        new_cache = jax.tree_util.tree_map(
            lambda a, rows: write(a, rows, cache_len), cache, new_cache)
    x = rms_norm(x, params["final_norm"])
    logits = _logits(params, x)
    out = (logits[:, -1].astype(jnp.float32), new_cache, cache_len + c)
    return (*out, hits) if route_stats else out


def decode_step(
    params: dict,
    cfg: LMConfig,
    tokens: jax.Array,  # [B,1]
    cache: dict,
    cache_len: jax.Array,  # [B] current valid length (new token goes here)
    *,
    unroll: int = 1,
    route_stats: bool = False,
    moe_dispatch: str = "dropless",
):
    """One decode step: ``prefill_chunk`` at width 1."""
    return prefill_chunk(params, cfg, tokens, cache, cache_len, unroll=unroll,
                         route_stats=route_stats, moe_dispatch=moe_dispatch)
