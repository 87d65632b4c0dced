"""Operations and bytes that a dense decoder's step programs need, and the
peaks of the chips they run on.

The count is of the work the algorithm needs, not of what a program happens
to do: a step reads every weight once, reads the embedding rows of its own
tokens and not the table, reads the cache up to its live length and writes
only its new slots, and computes logits only where a token is read from
them.  A program that does more reads as a smaller share of its roofline,
and one that stops doing needless work reads higher, never above 100%.
"""
from __future__ import annotations

from dataclasses import dataclass

from lm import Dims

# Google Cloud documentation, "TPU v5e": per chip, 197 TFLOP/s (bf16) and
# 819 GB/s of HBM bandwidth, 16 GB of HBM.  Keyed by jax's device_kind.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def seconds(self, peak: dict) -> float:
        """Least time on a chip: the larger of the compute and memory bounds."""
        return max(self.flops / peak["flops"], self.bytes / peak["bytes_per_s"])


def layer_weight_count(m: Dims) -> int:
    d, h, kv, e, f = m.d_model, m.n_heads, m.n_kv_heads, m.d_head, m.d_ff
    return d * (h + 2 * kv) * e + h * e * d + 3 * d * f + 2 * d


def step_work(m: Dims, cache_len: int, n_tokens: int, *, wbytes: int = 2,
              kv_bytes: int = 2, logit_rows: int = 1) -> Work:
    """One step over ``n_tokens`` new tokens after ``cache_len`` cached ones:
    a prefill chunk (``n_tokens`` = chunk) or a decode step (1)."""
    d, h, kv, e, f, V, L = (m.d_model, m.n_heads, m.n_kv_heads, m.d_head,
                            m.d_ff, m.vocab, m.n_layers)
    c, n = n_tokens, cache_len
    # keys each new token attends to: token j of the step sees n + j + 1
    attended = c * n + c * (c + 1) // 2
    per_layer_flops = (
        2 * c * d * (h + 2 * kv) * e  # q, k, v projections
        + 2 * 2 * h * e * attended  # scores and weighted values
        + 2 * c * h * e * d  # output projection
        + 3 * 2 * c * d * f  # gate, up, down
    )
    flops = L * per_layer_flops + 2 * logit_rows * d * V
    weights = (L * layer_weight_count(m) + d + d * V) * wbytes
    kv_slot = 2 * kv * e * kv_bytes  # one position's key and value, one layer
    cache = L * kv_slot * (n + c)  # read the live prefix, write c new slots
    io = c * d * wbytes + c * 4 + logit_rows * V * 4  # rows, ids, logits
    return Work(float(flops), float(weights + cache + io))


def prefill_work(m: Dims, cache_len: int, chunk: int) -> Work:
    return step_work(m, cache_len, chunk)


def decode_work(m: Dims, cache_len: int) -> Work:
    return step_work(m, cache_len, 1)
