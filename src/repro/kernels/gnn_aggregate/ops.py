"""jit'd wrapper for the gated neighbour aggregation kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import gnn_aggregate_kernel


@partial(jax.jit, static_argnames=("interpret",))
def gnn_aggregate(
    h: jax.Array,  # [N, dim]
    nbr: jax.Array,  # [N, max_deg]
    gates: jax.Array,  # [N, max_deg, dim]
    *,
    interpret: bool = False,
) -> jax.Array:
    N, dim = h.shape
    pad = (-dim) % 128
    if pad:
        h = jnp.pad(h, [(0, 0), (0, pad)])
        gates = jnp.pad(gates, [(0, 0), (0, 0), (0, pad)])
    out = gnn_aggregate_kernel(h, nbr, gates, interpret=interpret)
    return out[:, :dim]
