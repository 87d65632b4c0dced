"""train_step / serve_step builders for every architecture family.

``build_param_specs(cfg, cell)`` -> PSpec tree
``build_train_step(cfg, ...)``  -> fn(state, batch) -> (state, metrics)
``build_serve_step(cfg, cell)`` -> fn(params, **inputs) -> outputs

All functions are pure and jit-able; distribution comes from in/out shardings
applied by the launcher (GSPMD propagates through the step).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import Config, GNNConfig, LMConfig, RecsysConfig, ShapeCell
from ..models import gnn, recsys, transformer
from ..optim import AdamWConfig, apply_updates, init_state

REMAT_POLICIES: dict[str, Any] = {
    "none": None,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "full": jax.checkpoint_policies.nothing_saveable,
}


# --------------------------------------------------------------------------
# param specs
# --------------------------------------------------------------------------
def build_param_specs(cfg: Config, cell: ShapeCell | None = None) -> Any:
    if isinstance(cfg, LMConfig):
        return transformer.lm_specs(cfg)
    if isinstance(cfg, GNNConfig):
        d_feat = 16
        if cell is not None:
            d_feat = cell.params.get("d_feat", 602 if cell.kind == "minibatch" else 16)
        return gnn.gnn_specs(cfg, d_feat)
    if isinstance(cfg, RecsysConfig):
        return {
            "fm-2way": recsys.fm_specs,
            "cross": recsys.dcn_specs,
            "transformer-seq": recsys.bst_specs,
            "self-attn-seq": recsys.sasrec_specs,
        }[cfg.interaction](cfg)
    raise TypeError(type(cfg))


# --------------------------------------------------------------------------
# losses
# --------------------------------------------------------------------------
def _lm_loss(params, cfg: LMConfig, batch, *, remat=None, unroll=1):
    if cfg.loss_vocab_chunks:
        x, _ = transformer.forward(
            params, cfg, batch["tokens"], remat=remat, unroll=unroll, no_head=True,
            moe_dispatch="capacity",
        )
        head = params.get("head")
        if head is None:
            head = params["embed"].T
        loss = transformer.streaming_ce_loss(
            x, head, batch["targets"], cfg.loss_vocab_chunks
        )
        return loss, {"loss": loss, "ppl_proxy": loss}
    logits, _ = transformer.forward(
        params, cfg, batch["tokens"], remat=remat, unroll=unroll,
        moe_dispatch="capacity",  # training keeps the GShard dispatch
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)[..., 0]
    loss = ce.mean()
    return loss, {"loss": loss, "ppl_proxy": loss}


def _bce(logits, labels):
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def _gnn_loss(params, cfg: GNNConfig, batch, cell: ShapeCell, remat=None):
    if cell.kind == "batched_graphs":
        logits = gnn.forward_batched(params, cfg, batch["node_feat"], batch["edge_index"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1).mean()
        return loss, {"loss": loss}
    if cell.kind == "minibatch":
        logits = gnn.forward(params, cfg, batch["node_feat"], batch["edge_index"])
        seed_logits = logits[: batch["labels"].shape[0]]
        logp = jax.nn.log_softmax(seed_logits, axis=-1)
        loss = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1).mean()
        return loss, {"loss": loss}
    mask = batch.get("train_mask")
    loss = gnn.loss_fn(
        params, cfg, batch["node_feat"], batch["edge_index"], batch["labels"],
        mask.astype(jnp.float32) if mask is not None else None,
        remat=remat,
    )
    return loss, {"loss": loss}


def _recsys_loss(params, cfg: RecsysConfig, batch):
    if cfg.interaction == "fm-2way":
        logits = recsys.fm_forward(params, cfg, batch["sparse_ids"])
        loss = _bce(logits, batch["labels"])
    elif cfg.interaction == "cross":
        logits = recsys.dcn_forward(params, cfg, batch["dense"], batch["sparse_ids"])
        loss = _bce(logits, batch["labels"])
    elif cfg.interaction == "transformer-seq":
        logits = recsys.bst_forward(params, cfg, batch["hist_ids"], batch["target_id"])
        loss = _bce(logits, batch["labels"])
    elif cfg.interaction == "self-attn-seq":
        pos, neg = recsys.sasrec_forward(
            params, cfg, batch["hist_ids"], batch["pos_ids"], batch["neg_ids"]
        )
        loss = -jnp.mean(jax.nn.log_sigmoid(pos - neg))  # BPR
    else:
        raise ValueError(cfg.interaction)
    return loss, {"loss": loss}


def build_loss_fn(
    cfg: Config, cell: ShapeCell | None = None, *, remat: str = "none", unroll: int = 1
) -> Callable:
    if isinstance(cfg, LMConfig):
        policy = REMAT_POLICIES[remat]
        return lambda params, batch: _lm_loss(
            params, cfg, batch, remat=policy, unroll=unroll
        )
    if isinstance(cfg, GNNConfig):
        assert cell is not None
        policy = REMAT_POLICIES[remat]
        return lambda params, batch: _gnn_loss(params, cfg, batch, cell, remat=policy)
    if isinstance(cfg, RecsysConfig):
        return lambda params, batch: _recsys_loss(params, cfg, batch)
    raise TypeError(type(cfg))


# --------------------------------------------------------------------------
# train step
# --------------------------------------------------------------------------
def make_train_state(params: Any, opt_cfg: AdamWConfig | None = None) -> dict:
    return {"params": params, "opt": init_state(params)}


def build_train_step(
    cfg: Config,
    cell: ShapeCell | None = None,
    opt_cfg: AdamWConfig | None = None,
    *,
    remat: str = "none",
    unroll: int = 1,
    grad_accum: int = 1,
) -> Callable:
    """Returns fn(state, batch) -> (state, metrics)."""
    opt_cfg = opt_cfg or AdamWConfig()
    loss_fn = build_loss_fn(cfg, cell, remat=remat, unroll=unroll)

    def single_grads(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch), has_aux=True
        )(params)
        return loss, metrics, grads

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        params = state["params"]
        if grad_accum == 1:
            _, metrics, grads = single_grads(params, batch)
        else:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum) + x.shape[1:]),
                batch,
            )

            def accum(carry, mb):
                _, metrics, g = single_grads(params, mb)
                return (
                    jax.tree_util.tree_map(jnp.add, carry[0], g),
                    jax.tree_util.tree_map(jnp.add, carry[1], metrics),
                ), None

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            zero_m = {"loss": jnp.zeros((), jnp.float32)}
            if isinstance(cfg, LMConfig):
                zero_m["ppl_proxy"] = jnp.zeros((), jnp.float32)
            (grads, metrics), _ = jax.lax.scan(accum, (zero_g, zero_m), micro)
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
            metrics = jax.tree_util.tree_map(lambda m: m / grad_accum, metrics)
        new_params, new_opt, opt_metrics = apply_updates(
            params, grads, state["opt"], opt_cfg
        )
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


# --------------------------------------------------------------------------
# serve step
# --------------------------------------------------------------------------
def build_serve_step(cfg: Config, cell: ShapeCell, *, unroll: int = 1) -> Callable:
    # the dry-run's serving cells keep the GShard dispatch, whose einsums
    # GSPMD shards over the experts axis; the dropless dispatch's grouped
    # matmuls carry no expert-parallel layout yet (PERF.md)
    if isinstance(cfg, LMConfig):
        if cell.kind == "prefill":

            def prefill_step(params, tokens):
                return transformer.prefill(
                    params, cfg, tokens, unroll=unroll, moe_dispatch="capacity"
                )

            return prefill_step
        if cell.kind == "decode":

            def decode_step(params, tokens, cache, cache_len):
                return transformer.decode_step(
                    params, cfg, tokens, cache, cache_len, unroll=unroll,
                    moe_dispatch="capacity",
                )

            return decode_step
        raise ValueError(cell.kind)

    if isinstance(cfg, RecsysConfig):
        if cell.kind == "retrieval":
            fn = {
                "fm-2way": lambda p, **b: recsys.fm_retrieval(
                    p, cfg, b["sparse_ids"], b["candidate_ids"]
                ),
                "cross": lambda p, **b: recsys.dcn_retrieval(
                    p, cfg, b["dense"], b["sparse_ids"], b["candidate_ids"]
                ),
                "transformer-seq": lambda p, **b: recsys.bst_retrieval(
                    p, cfg, b["hist_ids"], b["candidate_ids"]
                ),
                "self-attn-seq": lambda p, **b: recsys.sasrec_retrieval(
                    p, cfg, b["hist_ids"], b["candidate_ids"]
                ),
            }[cfg.interaction]
            return fn

        def score(params, **batch):
            if cfg.interaction == "fm-2way":
                return recsys.fm_forward(params, cfg, batch["sparse_ids"])
            if cfg.interaction == "cross":
                return recsys.dcn_forward(params, cfg, batch["dense"], batch["sparse_ids"])
            if cfg.interaction == "transformer-seq":
                return recsys.bst_forward(params, cfg, batch["hist_ids"], batch["target_id"])
            if cfg.interaction == "self-attn-seq":
                pos, neg = recsys.sasrec_forward(
                    params, cfg, batch["hist_ids"], batch["pos_ids"], batch["neg_ids"]
                )
                return pos
            raise ValueError(cfg.interaction)

        return score

    raise TypeError(f"no serve step for {type(cfg)}")
