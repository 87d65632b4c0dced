"""Mixture-of-Experts FFN: shared + routed top-k.

Two dispatches share one router (``route``: softmax over all routed experts
in float32, plain or group-limited greedy top-k, gates renormalised or
scaled) and one shared-expert SwiGLU:

- ``moe_ffn_dropless`` (serving: ``ServeEngine``'s ``prefill_chunk`` and
  ``decode_step``, and ``transformer.forward`` / ``prefill`` /
  ``decode_step`` by default): tokens sorted by expert and grouped matmuls
  (``jax.lax.ragged_dot``) over the experts this chip holds (a decode
  step's few rows visit only the experts they reached).  No token is
  dropped at any batch or chunk size; assignments to experts held
  elsewhere add nothing here (expert parallelism's share).
- ``moe_ffn`` (``moe_dispatch="capacity"``: the training step and every
  dry-run cell): the grouped GShard/Switch einsum formulation — tokens are
reshaped into groups, a capacity-bounded one-hot dispatch tensor routes each
token to its top-k experts, and expert FFNs run as one stacked einsum over the
expert dimension.  Expert parallelism falls out of sharding the expert dim of
the weights ("experts" logical axis); the dispatch/combine einsums become the
all-to-alls.  Capacity factor bounds the dispatch tensor to O(k*T*g) — linear
in tokens (the ungrouped formulation is quadratic).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..configs.base import MoESpec
from .layers import PSpec


@dataclasses.dataclass(frozen=True)
class MoEDims:
    n_experts: int
    top_k: int
    n_shared: int
    d_model: int
    d_ff: int
    group_size: int = 1024
    capacity_factor: float = 1.25
    # optional explicit EP annotations (mesh axis names) — forces GSPMD to
    # reshard tokens->experts as an all-to-all at the dispatch boundary
    ep_axis: str | None = None
    token_axes: tuple = ()
    n_group: int = 1
    topk_group: int = 1
    norm_topk: bool = True
    routed_scale: float = 1.0
    held_first: int = 0
    n_held: int = 0  # 0: all n_experts

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts

    def capacity(self, group_size: int) -> int:
        c = int(math.ceil(self.top_k * group_size / self.n_experts * self.capacity_factor))
        return max(c, 4)


def moe_specs(spec: MoESpec, d_model: int, n_layers: int) -> dict:
    """The router spans all routed experts; the expert weights only those held."""
    E, Eh, f = spec.n_experts, spec.held, spec.d_ff_expert
    d = d_model
    L = n_layers
    out = {
        "router": PSpec((L, d, E), ("layers", "embed", "experts_r")),
        "w_gate": PSpec((L, Eh, d, f), ("layers", "experts", "embed", "expert_ff")),
        "w_up": PSpec((L, Eh, d, f), ("layers", "experts", "embed", "expert_ff")),
        "w_down": PSpec((L, Eh, f, d), ("layers", "experts", "expert_ff", "embed")),
    }
    if spec.n_shared:
        fs = spec.d_ff_expert * spec.n_shared
        out["shared"] = {
            "w_gate": PSpec((L, d, fs), ("layers", "embed", "ff")),
            "w_up": PSpec((L, d, fs), ("layers", "embed", "ff")),
            "w_down": PSpec((L, fs, d), ("layers", "ff", "embed")),
        }
    return out


def route(x: jax.Array, router: jax.Array, dims: MoEDims) -> tuple[jax.Array, jax.Array]:
    """x [..., d] -> (gates [..., K] float32, expert ids [..., K]).

    Softmax over all routed experts in float32.  With n_group > 1, DeepSeek's
    group-limited greedy: a group's score is its best expert's probability,
    the topk_group best groups are kept and the top-K are taken among their
    experts.  Gates are renormalised (norm_topk) or times routed_scale."""
    logits = jnp.einsum(
        "...d,dE->...E", x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    scores = probs
    if dims.n_group > 1:
        E, ng = dims.n_experts, dims.n_group
        grouped = probs.reshape(*probs.shape[:-1], ng, E // ng)
        _, top_groups = jax.lax.top_k(grouped.max(-1), dims.topk_group)
        keep = jnp.sum(jax.nn.one_hot(top_groups, ng, dtype=jnp.int32), axis=-2) > 0
        scores = jnp.where(keep[..., None], grouped, 0.0).reshape(probs.shape)
    gates, idx = jax.lax.top_k(scores, dims.top_k)
    if dims.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    else:
        gates = gates * dims.routed_scale
    return gates, idx


def shared_ffn(x: jax.Array, p: dict) -> jax.Array:
    """The shared experts, as one SwiGLU of width n_shared * d_ff_expert."""
    with jax.named_scope("moe.shared"):
        sp = p["shared"]
        gsh = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, sp["w_gate"]))
        ush = jnp.einsum("bsd,df->bsf", x, sp["w_up"])
        return jnp.einsum("bsf,fd->bsd", gsh * ush, sp["w_down"])


# a decode step's few rows: visiting only the experts some row reached reads
# those experts' weights alone, where the grouped matmul reads every one
FEW_ROWS = 64


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def expert_weights(p: dict, k: str, *idx) -> jax.Array:
    """Held-expert weights ``p[k]`` at the leading indices ``idx``.  Where
    ``p["layer"]`` is given, ``p[k]`` is every layer's stack and is indexed
    at that layer first: a scan then hands the stack to the experts whole,
    and a decode step's slice of one expert is read where it is used instead
    of the layer's experts being copied out of the stack first."""
    w = p[k]
    lead = ((p["layer"],) if "layer" in p else ()) + idx
    if not lead:
        return w
    tail = w.shape[len(lead):]
    start = lead + (0,) * len(tail)
    return jax.lax.dynamic_slice(w, start, (1,) * len(lead) + tail).reshape(tail)


def _visit_experts(rows, slot, sizes, p):
    """Each held expert that some row was routed to, on all the rows, kept
    where the row is its own: [rows, d] (a row of no held expert reads 0)."""

    def visit(e, y):
        def compute(y):
            w = {k: expert_weights(p, k, e) for k in EXPERT_WEIGHTS}
            h = jax.nn.silu(rows @ w["w_gate"]) * (rows @ w["w_up"])
            return y + jnp.where((slot == e)[:, None], h @ w["w_down"], 0)

        return jax.lax.cond(sizes[e] > 0, compute, lambda y: y, y)

    return jax.lax.fori_loop(0, sizes.shape[0], visit, jnp.zeros_like(rows))


def moe_ffn_dropless(x: jax.Array, p: dict, dims: MoEDims) -> tuple[jax.Array, jax.Array]:
    """x: [B,S,d] -> ([B,S,d], hits [held + 1] int32).

    Every token's top-K assignments are routed; those to the held experts
    [held_first, held_first + held) are computed by grouped matmuls over the
    tokens sorted by expert, the rest add nothing here.  ``hits`` counts the
    assignments per held expert, and in its last entry those to experts held
    elsewhere."""
    B, S, d = x.shape
    K, Eh = dims.top_k, dims.held
    T = B * S
    xt = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        gates, idx = route(xt, p["router"], dims)  # [T,K]
        local = idx.reshape(-1) - dims.held_first  # [T*K]
        mine = (local >= 0) & (local < Eh)
        slot = jnp.where(mine, local, Eh)  # not held: sorts last
        order = jnp.argsort(slot, stable=True)
        hits = jnp.bincount(slot, length=Eh + 1).astype(jnp.int32)
        rows = jnp.take(xt, order // K, axis=0)  # [T*K, d], by expert
        gate = jnp.where(mine, gates.reshape(-1), 0.0)[order]
        valid = mine[order]
    with jax.named_scope("moe.experts"):
        sizes = hits[:Eh]
        if T * K <= FEW_ROWS:
            y = _visit_experts(rows, slot[order], sizes, p)
        else:
            w = {k: expert_weights(p, k) for k in EXPERT_WEIGHTS}
            h = jax.nn.silu(jax.lax.ragged_dot(rows, w["w_gate"], sizes))
            h = h * jax.lax.ragged_dot(rows, w["w_up"], sizes)
            y = jax.lax.ragged_dot(h, w["w_down"], sizes)  # [T*K, d]
        # rows past the held groups are no group's: zero them, not scale them
        y = jnp.where(valid[:, None], y.astype(jnp.float32) * gate[:, None], 0.0)
        # back to token order (a gather), then each token's K rows summed
        unsort = jnp.zeros_like(order).at[order].set(jnp.arange(T * K, dtype=order.dtype))
        out = y[unsort].reshape(T, K, d).sum(axis=1).astype(x.dtype)
    out = out.reshape(B, S, d)
    if "shared" in p:
        out = out + shared_ffn(x, p)
    return out, hits


def moe_ffn(x: jax.Array, p: dict, dims: MoEDims) -> jax.Array:
    """x: [B,S,d] -> [B,S,d].  p holds one layer's slices (no leading L),
    the experts' possibly as every layer's stack (``expert_weights``)."""
    w = {k: expert_weights(p, k) for k in EXPERT_WEIGHTS}
    B, S, d = x.shape
    E, K = dims.n_experts, dims.top_k
    g = min(dims.group_size, B * S)
    T = B * S
    assert T % g == 0, (T, g)
    G = T // g
    C = dims.capacity(g)

    xt = x.reshape(G, g, d)
    gates, idx = route(xt, p["router"], dims)  # [G,g,K]

    # capacity-bounded positions, priority by k (GShard top-k dispatch)
    dispatch = jnp.zeros((G, g, E, C), dtype=x.dtype)
    combine = jnp.zeros((G, g, E, C), dtype=x.dtype)
    prior_count = jnp.zeros((G, 1, E), dtype=jnp.int32)
    for k in range(K):
        mask_k = jax.nn.one_hot(idx[..., k], E, dtype=jnp.int32)  # [G,g,E]
        pos_k = jnp.cumsum(mask_k, axis=1) - 1 + prior_count  # [G,g,E]
        prior_count = prior_count + mask_k.sum(axis=1, keepdims=True)
        keep = (pos_k < C) & (mask_k > 0)
        oh = jax.nn.one_hot(jnp.where(keep, pos_k, C), C, dtype=x.dtype)
        d_k = oh * keep.astype(x.dtype)[..., None]  # [G,g,E,C]
        dispatch = dispatch + d_k
        combine = combine + d_k * gates[..., k, None, None].astype(x.dtype)
    if dims.held != E:  # experts held elsewhere add nothing here
        lo, hi = dims.held_first, dims.held_first + dims.held
        dispatch, combine = dispatch[:, :, lo:hi], combine[:, :, lo:hi]

    def _ep_constraint(t):
        if dims.ep_axis is None:
            return t
        from jax.sharding import PartitionSpec as P

        spec = P(dims.ep_axis, dims.token_axes or None, *([None] * (t.ndim - 2)))
        return jax.lax.with_sharding_constraint(t, spec)

    expert_in = _ep_constraint(jnp.einsum("GgEC,Ggd->EGCd", dispatch, xt))
    h = jax.nn.silu(jnp.einsum("EGCd,Edf->EGCf", expert_in, w["w_gate"]))
    h = h * jnp.einsum("EGCd,Edf->EGCf", expert_in, w["w_up"])
    expert_out = _ep_constraint(jnp.einsum("EGCf,Efd->EGCd", h, w["w_down"]))
    y = jnp.einsum("GgEC,EGCd->Ggd", combine, expert_out)

    out = y.reshape(B, S, d)
    if "shared" in p:
        out = out + shared_ffn(x, p)
    return out


def aux_load_balance_loss(logits: jax.Array, idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    density = jnp.mean(
        jax.nn.one_hot(idx[..., 0], n_experts, dtype=jnp.float32), axis=(0, 1)
    )
    density_proxy = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    return n_experts * jnp.sum(density * density_proxy)
