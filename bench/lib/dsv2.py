"""Seeded weights, the plain float32 reference, and the work counts of
DeepSeek-V2 (arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2 config.json) at
one chip's share of its experts.

The equations, written out in ``jax.numpy`` with no cache, no chunks, no
sorting and no kernels:

- pre-norm decoder layers: ``x += attn(rms(x))``, ``x += ffn(rms(x))``;
- latent attention: ``c_q = rms(x W_dq)``, ``q = c_q W_uq`` split into 128
  nope and 64 rope dims per head; ``[c_kv, k_pe] = x W_dkv``,
  ``c_kv = rms(c_kv)``, ``k_nope = c_kv W_uk``, ``v = c_kv W_uv``; rope on
  ``q_pe`` and the one shared ``k_pe`` after DeepSeek's interleaved-to-halves
  permutation, with YaRN frequencies (HF ``DeepseekV2YarnRotaryEmbedding``)
  and cos/sin times ``mscale(f, mscale) / mscale(f, mscale_all_dim)``;
  softmax scale ``(nope + rope)^-1/2 * mscale(f, mscale_all_dim)^2``;
- layers before ``first_k_dense_replace``: a SwiGLU of width
  ``intermediate_size``; the others: a softmax router over all routed
  experts (float32), group-limited greedy top-k (a group's score is its best
  expert's probability; ``topk_group`` groups kept), gates not renormalised
  and times ``routed_scaling_factor``, plus the shared experts as one SwiGLU
  of width ``n_shared_experts * moe_intermediate_size``.

Departures from the published model, each shared with the program: the
experts are one chip's share, experts ``[held_first, held_first + held)``;
assignments to the others add nothing (they would be computed on the
deployment's other chips).  Each RMSNorm weight is ``1 + delta`` (the draw is
``delta``, as the program stores it).  Weights are random, drawn here from
``--seed`` one layer at a time, so the reference draws any layer again
without taking anything from the program.

The reference attends blocked over queries, so a 10k-token sequence fits on
one chip beside nothing else.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from flops import Work
from lm import EMBED_STD, NORM_STD, _draw, _mm, root_key


@dataclass(frozen=True)
class Dims:
    """Sizes of DeepSeek-V2, read from a Hugging Face ``config.json``."""

    n_layers: int
    first_k_dense: int
    d_model: int
    n_heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int  # the dense layers' width
    d_expert: int
    n_routed: int  # the router's width
    held_first: int
    n_held: int
    top_k: int
    n_group: int
    topk_group: int
    n_shared: int
    routed_scale: float
    norm_topk: bool
    vocab: int
    rms_eps: float
    rope_theta: float
    yarn: tuple  # (factor, beta_fast, beta_slow, original max position, mscale, mscale_all_dim)

    @classmethod
    def from_hf(cls, cfg: dict) -> "Dims":
        """``n_routed_experts`` counts the experts held here; the router's
        width is ``deployment.n_routed_experts`` where the file states it."""
        dep = cfg.get("deployment", {})
        rs = cfg["rope_scaling"]
        return cls(
            n_layers=cfg["num_hidden_layers"],
            first_k_dense=cfg["first_k_dense_replace"],
            d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            q_lora=cfg["q_lora_rank"],
            kv_lora=cfg["kv_lora_rank"],
            nope=cfg["qk_nope_head_dim"],
            rope=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"],
            d_ff=cfg["intermediate_size"],
            d_expert=cfg["moe_intermediate_size"],
            n_routed=dep.get("n_routed_experts", cfg["n_routed_experts"]),
            held_first=dep.get("held_first", 0),
            n_held=cfg["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            n_group=cfg["n_group"],
            topk_group=cfg["topk_group"],
            n_shared=cfg["n_shared_experts"],
            routed_scale=float(cfg["routed_scaling_factor"]),
            norm_topk=bool(cfg["norm_topk_prob"]),
            vocab=cfg["vocab_size"],
            rms_eps=cfg["rms_norm_eps"],
            rope_theta=float(cfg["rope_theta"]),
            yarn=(float(rs["factor"]), float(rs["beta_fast"]), float(rs["beta_slow"]),
                  int(rs["original_max_position_embeddings"]), float(rs["mscale"]),
                  float(rs["mscale_all_dim"])),
        )

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_k_dense


# -- weights ---------------------------------------------------------------------
def _attn_shapes(m: Dims) -> dict:
    d, h, e = m.d_model, m.n_heads, m.nope + m.rope
    return {
        "ln1": ((d,), NORM_STD),
        "ln2": ((d,), NORM_STD),
        "w_dq": ((d, m.q_lora), d**-0.5),
        "q_norm": ((m.q_lora,), NORM_STD),
        "w_uq": ((m.q_lora, h, e), m.q_lora**-0.5),
        "w_dkv": ((d, m.kv_lora + m.rope), d**-0.5),
        "kv_norm": ((m.kv_lora,), NORM_STD),
        "w_uk": ((m.kv_lora, h, m.nope), m.kv_lora**-0.5),
        "w_uv": ((m.kv_lora, h, m.v_dim), m.kv_lora**-0.5),
        "w_o": ((h * m.v_dim, d), (h * m.v_dim) ** -0.5),
    }


def _dense_shapes(m: Dims) -> dict:
    d, f = m.d_model, m.d_ff
    return {**_attn_shapes(m), "w_gate": ((d, f), d**-0.5), "w_up": ((d, f), d**-0.5),
            "w_down": ((f, d), f**-0.5)}


def _moe_shapes(m: Dims) -> dict:
    d, f, eh, fs = m.d_model, m.d_expert, m.n_held, m.n_shared * m.d_expert
    return {
        **_attn_shapes(m),
        "router": ((d, m.n_routed), d**-0.5),
        "e_gate": ((eh, d, f), d**-0.5),
        "e_up": ((eh, d, f), d**-0.5),
        "e_down": ((eh, f, d), f**-0.5),
        "s_gate": ((d, fs), d**-0.5),
        "s_up": ((d, fs), d**-0.5),
        "s_down": ((fs, d), fs**-0.5),
    }


def _top_shapes(m: Dims) -> dict:
    d, v = m.d_model, m.vocab
    return {"embed": ((v, d), EMBED_STD), "final_norm": ((d,), NORM_STD),
            "head": ((d, v), d**-0.5)}


def _layer_key(key, layer: int):
    return jax.random.fold_in(jax.random.fold_in(key, 1), layer)


@partial(jax.jit, static_argnames=("m", "dense", "dtype"))
def _draw_layer(key, m: Dims, dense: bool, dtype):
    return _draw(key, _dense_shapes(m) if dense else _moe_shapes(m), dtype)


def layer_weights(seed: int, m: Dims, layer: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``layer``'s weights, the same values the program is given."""
    return _draw_layer(_layer_key(root_key(seed), layer), m, layer < m.first_k_dense, dtype)


def top_weights(seed: int, m: Dims, dtype=jnp.bfloat16) -> dict:
    return _draw_top(jax.random.fold_in(root_key(seed), 0), m, dtype)


@partial(jax.jit, static_argnames=("m", "dtype"))
def _draw_top(key, m: Dims, dtype):
    return _draw(key, _top_shapes(m), dtype)


def _program_layers(ly: dict, dense: bool) -> dict:
    """One stack of layers in ``repro.models.transformer``'s layout."""
    attn = ("w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_uk", "w_uv", "w_o")
    out = {"ln1": ly["ln1"], "ln2": ly["ln2"], "attn": {n: ly[n] for n in attn}}
    if dense:
        out["ffn"] = {n: ly[n] for n in ("w_gate", "w_up", "w_down")}
    else:
        out["moe"] = {
            "router": ly["router"],
            "w_gate": ly["e_gate"], "w_up": ly["e_up"], "w_down": ly["e_down"],
            "shared": {"w_gate": ly["s_gate"], "w_up": ly["s_up"], "w_down": ly["s_down"]},
        }
    return out


def program_params(seed: int, m: Dims, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout ``repro.models.transformer``
    takes (leading dense layers under ``lead``), drawn on the device."""

    @jax.jit
    def build(key):
        top = _draw(jax.random.fold_in(key, 0), _top_shapes(m), dtype)

        def stack(layers, dense):
            keys = jax.vmap(lambda i: _layer_key(key, i))(jnp.asarray(list(layers)))
            shapes = _dense_shapes(m) if dense else _moe_shapes(m)
            return _program_layers(jax.vmap(lambda k: _draw(k, shapes, dtype))(keys), dense)

        out = {**top, "layers": stack(range(m.first_k_dense, m.n_layers), False)}
        if m.first_k_dense:
            out["lead"] = stack(range(m.first_k_dense), True)
        return out

    return build(root_key(seed))


def program_config(m: Dims, name: str, dtype):
    """The program's ``LMConfig`` for these sizes (held experts included)."""
    from repro.configs.base import LMConfig, MLASpec, MoESpec, YarnSpec

    f, bf, bs, orig, ms, msa = m.yarn
    return LMConfig(
        name=name, n_layers=m.n_layers, d_model=m.d_model, n_heads=m.n_heads,
        n_kv_heads=m.n_heads, d_head=m.nope + m.rope, d_ff=m.d_ff, vocab=m.vocab,
        moe=MoESpec(
            n_experts=m.n_routed, top_k=m.top_k, n_shared=m.n_shared,
            d_ff_expert=m.d_expert, n_group=m.n_group, topk_group=m.topk_group,
            norm_topk=m.norm_topk, routed_scale=m.routed_scale,
            held_first=m.held_first, n_held=m.n_held,
        ),
        mla=MLASpec(kv_lora_rank=m.kv_lora, q_lora_rank=m.q_lora, rope_head_dim=m.rope,
                    nope_head_dim=m.nope, v_head_dim=m.v_dim),
        rope_scaling=YarnSpec(factor=f, beta_fast=bf, beta_slow=bs,
                              original_max_position=orig, mscale=ms, mscale_all_dim=msa),
        first_k_dense=m.first_k_dense, rope_theta=m.rope_theta, dtype=dtype,
    )


# -- the reference -------------------------------------------------------------------
# ``quant=True`` is the control: every matrix product takes operands rounded
# to float8 (``lm._mm``), the precision below the bfloat16 served.
def _rms(x, delta, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + delta)


def _yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(m: Dims) -> np.ndarray:
    """HF ``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    factor, beta_fast, beta_slow, orig, _, _ = m.yarn
    dim, base = m.rope, m.rope_theta

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(m: Dims) -> float:
    factor, *_, mscale_all_dim = m.yarn
    return (m.nope + m.rope) ** -0.5 * _yarn_get_mscale(factor, mscale_all_dim) ** 2


def _rope(x, pos, m: Dims):
    """x [S, H, rope] in DeepSeek's interleaved pairs."""
    S, H, e = x.shape
    x = x.reshape(S, H, e // 2, 2).transpose(0, 1, 3, 2).reshape(S, H, e)
    factor, _, _, _, mscale, mscale_all = m.yarn
    amp = _yarn_get_mscale(factor, mscale) / _yarn_get_mscale(factor, mscale_all)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(yarn_inv_freq(m))[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    cos, sin = (jnp.cos(ang) * amp)[:, None, :], (jnp.sin(ang) * amp)[:, None, :]
    rot = jnp.concatenate([-x[..., e // 2:], x[..., : e // 2]], axis=-1)
    return x * cos + rot * sin


def _attention(x, w, m: Dims, quant: bool, block_q: int):
    """Causal latent attention over x [S, d], blocked over queries."""
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], m.rms_eps)
    cq = _rms(_mm("sd,dr->sr", h, w["w_dq"], quant), w["q_norm"], m.rms_eps)
    q = _mm("sr,rhe->she", cq, w["w_uq"], quant)
    q = jnp.concatenate([q[..., : m.nope], _rope(q[..., m.nope:], pos, m)], axis=-1)
    ckv = _mm("sd,dr->sr", h, w["w_dkv"], quant)
    c = _rms(ckv[:, : m.kv_lora], w["kv_norm"], m.rms_eps)
    k_pe = _rope(ckv[:, None, m.kv_lora:], pos, m)
    k_nope = _mm("sr,rhe->she", c, w["w_uk"], quant)
    v = _mm("sr,rhe->she", c, w["w_uv"], quant)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:2] + (m.rope,))], -1)
    scale = softmax_scale(m)

    def one_block(args):
        qb, start = args
        s = _mm("qhe,the->hqt", qb, k, quant, a_axis=-1, b_axis=-1) * scale
        causal = pos[None, :] <= (start + jnp.arange(block_q))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return _mm("hqt,the->qhe", p, v, quant, a_axis=-1, b_axis=0)

    nb = S // block_q
    o = jax.lax.map(one_block, (q.reshape(nb, block_q, *q.shape[1:]),
                                jnp.arange(nb) * block_q))
    o = o.reshape(S, m.n_heads * m.v_dim)
    return x + _mm("se,ed->sd", o, w["w_o"], quant)


def route(h, router, m: Dims, quant: bool = False):
    """Gates [S, n_routed]: each token's top-k weights (not renormalised,
    times the routed scale) at its experts, 0 elsewhere."""
    probs = jax.nn.softmax(_mm("sd,de->se", h, router, quant), axis=-1)
    S, E = probs.shape
    group_best = probs.reshape(S, m.n_group, E // m.n_group).max(-1)
    kth = jnp.sort(group_best, axis=-1)[:, -m.topk_group][:, None]
    keep = jnp.repeat(group_best >= kth, E // m.n_group, axis=-1)
    masked = jnp.where(keep, probs, 0.0)
    kth_e = jnp.sort(masked, axis=-1)[:, -m.top_k][:, None]
    chosen = masked >= kth_e
    gates = jnp.where(chosen, probs, 0.0)
    if m.norm_topk:
        return gates / gates.sum(-1, keepdims=True)
    return gates * m.routed_scale


def _swiglu(h, wg, wu, wd, quant):
    g = jax.nn.silu(_mm("sd,df->sf", h, wg, quant))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", h, wu, quant), wd, quant)


@partial(jax.jit, static_argnames=("m", "quant", "block_q"))
def _dense_layer(x, w, m: Dims, quant: bool, block_q: int):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = _attention(x, w, m, quant, block_q)
    h = _rms(x, w["ln2"], m.rms_eps)
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)


def moe_ffn(h, w, m: Dims, quant: bool = False, experts=None, gates=None):
    """The held experts' share of the MoE FFN plus the shared experts, over
    h [S, d]; ``experts`` limits the routed part to some held experts, and
    ``gates`` gives the router's (``route``) where they are at hand."""
    if gates is None:
        gates = route(h, w["router"], m, quant)
    out = _swiglu(h, w["s_gate"], w["s_up"], w["s_down"], quant)
    for j in (range(m.n_held) if experts is None else experts):
        y = _swiglu(h, w["e_gate"][j], w["e_up"][j], w["e_down"][j], quant)
        out = out + gates[:, m.held_first + j, None] * y
    return out


@partial(jax.jit, static_argnames=("m", "quant", "block_q"))
def _moe_layer(x, w, m: Dims, quant: bool, block_q: int):
    """(x after the layer, whether each token chose each held expert [S, held])."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = _attention(x, w, m, quant, block_q)
    h = _rms(x, w["ln2"], m.rms_eps)
    gates = route(h, w["router"], m, quant)
    held = gates[:, m.held_first: m.held_first + m.n_held] > 0
    return x + moe_ffn(h, w, m, quant, gates=gates), held


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, rows, top, m: Dims, quant: bool):
    h = _rms(x[rows], top["final_norm"].astype(jnp.float32), m.rms_eps)
    return _mm("sd,dv->sv", h, top["head"].astype(jnp.float32), quant)


def reference_logits(seed: int, m: Dims, sequences, rows, *, quant: bool = False,
                     dtype=jnp.bfloat16, block_q: int = 256, routes: bool = False):
    """Logits [len(rows[i]), vocab] at positions ``rows[i]`` of each sequence;
    with ``routes``, also which held experts each of those positions chose in
    each MoE layer, [n_moe, len(rows[i]), held] booleans per sequence.

    Sequences are right-padded with token 0 to one length, a multiple of
    ``block_q`` (causal attention never lets an earlier row see the pads),
    so one compiled layer serves all; the weights are drawn again one layer
    at a time.  ``dtype`` is the one the weights are served in."""
    longest = max(len(s) for s in sequences)
    length = -(-longest // block_q) * block_q
    top = top_weights(seed, m, dtype)
    xs = []
    for s in sequences:
        tok = np.zeros(length, np.int32)
        tok[: len(s)] = s
        xs.append(top["embed"][jnp.asarray(tok)].astype(jnp.float32))
    rows = [jnp.asarray(r, jnp.int32) for r in rows]
    chosen = [[] for _ in xs]
    for layer in range(m.n_layers):
        w = layer_weights(seed, m, layer, dtype)
        if layer < m.first_k_dense:
            xs = [_dense_layer(x, w, m, quant, block_q) for x in xs]
        else:
            outs = [_moe_layer(x, w, m, quant, block_q) for x in xs]
            xs = [x for x, _ in outs]
            for c, (_, held), r in zip(chosen, outs, rows):
                c.append(held[r])
        del w
    logits = [np.asarray(_head(x, r, top, m, quant)) for x, r in zip(xs, rows)]
    if routes:
        return logits, [np.asarray(jnp.stack(c)) for c in chosen]
    return logits


# -- work that the step programs need ------------------------------------------------
# Lower bounds of what a step must do: every weight read once a call (a held
# expert's only where some token of the call reached it in that layer), the
# cache read up to its live length and written at its new slots, attention
# in the cheaper of its two forms, routed experts by the program's own
# assignment counts, logits only where a token is read from them.
def attention_core(m: Dims, cache_len: int, c: int, *, layers: int = 1) -> Work:
    """Latent attention from the cached latents to each head's context (the
    program's ``mla.attend``) in ``layers`` layers: decompressed or absorbed,
    whichever needs fewer operations."""
    h, r, n = m.n_heads, m.kv_lora, cache_len
    pairs = c * n + c * (c + 1) // 2  # keys the step's queries attend to
    decompressed = (2 * (n + c) * r * h * (m.nope + m.v_dim)
                    + 2 * pairs * h * (m.nope + m.rope) + 2 * pairs * h * m.v_dim)
    absorbed = (2 * c * h * m.nope * r + 2 * pairs * h * (r + m.rope)
                + 2 * pairs * h * r + 2 * c * h * r * m.v_dim)
    cache = (n + c) * (r + m.rope) * 2
    up = r * h * (m.nope + m.v_dim) * 2  # W_uk and W_uv
    io = c * h * (m.nope + m.rope) * 2 + c * h * m.v_dim * 2  # queries in, context out
    return Work(float(layers * min(decompressed, absorbed)), float(layers * (cache + up + io)))


def attention_weight_count(m: Dims) -> int:
    d, h = m.d_model, m.n_heads
    return (d * m.q_lora + m.q_lora + m.q_lora * h * (m.nope + m.rope)
            + d * (m.kv_lora + m.rope) + m.kv_lora + h * m.v_dim * d + 2 * d)


def routed_work(m: Dims, hits) -> Work:
    """Held experts' routed work for one call: ``hits`` [n_moe, held + 1]
    are the program's assignments per held expert (last: held elsewhere)."""
    hits = np.asarray(hits)[:, : m.n_held]
    per = 3 * m.d_model * m.d_expert
    rows = int(hits.sum())
    return Work(float(2 * per * rows),
                float(per * 2 * int((hits > 0).sum()) + rows * m.d_model * 2 * 2))


def step_work(m: Dims, cache_len: int, c: int, hits, *, logit_rows: int = 1) -> Work:
    """One step over ``c`` new tokens after ``cache_len`` cached ones, with
    the call's route counts ``hits``."""
    d, h, V = m.d_model, m.n_heads, m.vocab
    proj = 2 * c * (d * m.q_lora + m.q_lora * h * (m.nope + m.rope)
                    + d * (m.kv_lora + m.rope) + h * m.v_dim * d)
    attn = attention_core(m, cache_len, c, layers=m.n_layers)
    shared = 3 * d * m.n_shared * m.d_expert
    flops = (m.n_layers * proj + attn.flops
             + m.first_k_dense * 2 * c * 3 * d * m.d_ff
             + m.n_moe * 2 * c * (shared + d * m.n_routed)
             + 2 * logit_rows * d * V)
    weights = (m.n_layers * attention_weight_count(m) + m.first_k_dense * 3 * d * m.d_ff
               + m.n_moe * (shared + d * m.n_routed) + d + d * V) * 2
    cache = m.n_layers * (cache_len + c) * (m.kv_lora + m.rope) * 2
    io = c * d * 2 + c * 4 + logit_rows * V * 4
    return Work(float(flops), float(weights + cache + io)) + routed_work(m, hits)
