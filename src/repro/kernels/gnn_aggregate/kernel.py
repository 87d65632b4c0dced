"""Gated neighbour aggregation (GatedGCN inner loop) as a Pallas kernel.

out[n] = sum_j gate[n,j,:] * h[nbr[n,j],:]   (padded-neighbour / ELL layout)

TPU mapping: like the embedding-bag gather, the neighbour table is scalar-
prefetched and drives the feature-row DMA via the BlockSpec index_map; the
per-edge vector gates stream through a regular (1,1,dim) block.  Grid
(n_nodes, max_degree), degree innermost accumulating in VMEM scratch.
Padding slots point at row 0 with zero gates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _agg_kernel(nbr_ref, h_ref, gate_ref, o_ref, acc_scr, *, max_deg: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += h_ref[0, :].astype(jnp.float32) * gate_ref[0, 0, :].astype(
        jnp.float32
    )

    @pl.when(j == max_deg - 1)
    def _done():
        o_ref[0, :] = acc_scr[...].astype(o_ref.dtype)


def gnn_aggregate_kernel(
    h: jax.Array,  # [N, dim] node features (dim padded to 128)
    nbr: jax.Array,  # [N, max_deg] neighbour ids (pad -> 0)
    gates: jax.Array,  # [N, max_deg, dim] per-edge gates (pad -> 0)
    *,
    interpret: bool = False,
) -> jax.Array:
    N, dim = h.shape
    max_deg = nbr.shape[1]
    kernel = functools.partial(_agg_kernel, max_deg=max_deg)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, max_deg),
        in_specs=[
            pl.BlockSpec((1, dim), lambda n, j, nbr: (nbr[n, j], 0)),
            pl.BlockSpec((1, 1, dim), lambda n, j, nbr: (n, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, dim), lambda n, j, nbr: (n, 0)),
        scratch_shapes=[pltpu.VMEM((dim,), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, dim), h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(nbr.astype(jnp.int32), h, gates)
