"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle).  The wrappers compile via Mosaic for
the TPU; ``interpret=True`` runs them in the Pallas interpreter instead,
which is how the CPU tests call them.
"""
from .decode_attention import decode_attention, decode_attention_ref
from .embedding_bag import embedding_bag, embedding_bag_ref
from .flash_attention import attention_ref, flash_attention
from .gnn_aggregate import edge_to_padded, gnn_aggregate, gnn_aggregate_ref

__all__ = [
    "attention_ref",
    "decode_attention",
    "decode_attention_ref",
    "edge_to_padded",
    "embedding_bag",
    "embedding_bag_ref",
    "flash_attention",
    "gnn_aggregate",
    "gnn_aggregate_ref",
]
