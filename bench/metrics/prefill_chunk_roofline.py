"""The prefill-chunk program's share of its roofline, over the traced window:
for each call the larger of (operations needed / peak FLOP/s) and (bytes
needed / peak bandwidth), summed, over the program's device time."""
from layerreads import roofline


def read(d):
    return roofline(d, "prefill", "step.prefill_chunk")
