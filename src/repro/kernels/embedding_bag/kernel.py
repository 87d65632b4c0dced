"""EmbeddingBag gather-reduce — the recsys hot path as a Pallas kernel.

TPU mapping: the bag indices are scalar-prefetched (SMEM) and drive the
*index_map* of the table's BlockSpec — each grid step DMAs exactly one
(1, dim) table row from HBM into VMEM (the canonical Pallas sparse-gather
pattern; FBGEMM TBE equivalent).  Grid (n_bags, bag_size) with the bag-item
dimension innermost/sequential accumulating into a VMEM scratch row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(
    ids_ref,  # scalar-prefetch: [n_bags, bag_size] int32
    wgt_ref,  # scalar-prefetch: [n_bags, bag_size] f32 per-sample weights
    row_ref,  # [1, dim] — the gathered table row (DMA'd by index_map)
    o_ref,  # [1, dim]
    acc_scr,  # [dim] f32
    *,
    bag_size: int,
    combiner: str,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    w = wgt_ref[b, j]
    acc_scr[...] += row_ref[0, :].astype(jnp.float32) * w

    @pl.when(j == bag_size - 1)
    def _done():
        out = acc_scr[...]
        if combiner == "mean":
            out = out / bag_size
        o_ref[0, :] = out.astype(o_ref.dtype)


def embedding_bag_kernel(
    table: jax.Array,  # [V, dim]  (dim padded to 128)
    ids: jax.Array,  # [n_bags, bag_size] int32
    weights: jax.Array,  # [n_bags, bag_size] f32
    *,
    combiner: str = "sum",
    interpret: bool = False,
) -> jax.Array:
    n_bags, bag_size = ids.shape
    dim = table.shape[1]
    kernel = functools.partial(_bag_kernel, bag_size=bag_size, combiner=combiner)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_bags, bag_size),
        in_specs=[
            # the scalar-prefetched ids drive the gather: row = table[ids[b,j]]
            pl.BlockSpec((1, dim), lambda b, j, ids, wgt: (ids[b, j], 0)),
        ],
        out_specs=pl.BlockSpec((1, dim), lambda b, j, ids, wgt: (b, 0)),
        scratch_shapes=[pltpu.VMEM((dim,), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_bags, dim), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(ids, weights, table)
