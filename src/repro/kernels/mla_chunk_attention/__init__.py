from .ops import mla_chunk_attention
from .ref import mla_chunk_attention_ref

__all__ = ["mla_chunk_attention", "mla_chunk_attention_ref"]
