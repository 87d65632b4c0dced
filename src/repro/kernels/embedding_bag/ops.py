"""jit'd wrapper: dim padding + weight defaulting for the bag kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import embedding_bag_kernel


@partial(jax.jit, static_argnames=("combiner", "interpret"))
def embedding_bag(
    table: jax.Array,  # [V, dim]
    ids: jax.Array,  # [n_bags, bag_size]
    weights: jax.Array | None = None,
    *,
    combiner: str = "sum",
    interpret: bool = False,
) -> jax.Array:
    V, dim = table.shape
    pad = (-dim) % 128  # TPU lane alignment
    tp = jnp.pad(table, [(0, 0), (0, pad)]) if pad else table
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    out = embedding_bag_kernel(
        tp, ids.astype(jnp.int32), weights.astype(jnp.float32),
        combiner=combiner, interpret=interpret,
    )
    return out[:, :dim]
