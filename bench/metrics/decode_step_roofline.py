"""The decode-step program's share of its roofline over the traced window,
counted as ``prefill_chunk_roofline`` counts the prefill chunks: for each
call the larger of (operations needed / peak FLOP/s) and (bytes needed /
peak bandwidth), summed, over the program's device time."""
from layerreads import roofline


def read(d):
    return roofline(d, "decode", "step.decode_step")
