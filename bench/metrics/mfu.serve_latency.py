"""Whole-step model FLOP/s utilization over the traced window: the operations
that the prompt and output tokens of the requests finished in it need, over
the trace's window length x peak FLOP/s."""
from layerreads import mfu


def read(d):
    return mfu(d)
