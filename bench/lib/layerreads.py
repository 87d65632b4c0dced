"""Arithmetic that more than one per-layer reader shares."""


def roofline(d, kind: str, span: str):
    """The least time the chip could take for the traced calls of one step
    program, over the device time the trace gives that program, in %."""
    tr = d.get("trace")
    if d.get("kind") != "serve" or tr is None or not tr.step_device_s.get(span):
        return None
    least = sum(work.seconds(d["peak"]) for k, work in d["calls_in_trace"] if k == kind)
    if not least:
        return None
    return 100.0 * least / tr.step_device_s[span]


def mfu(d):
    """Operations that the prompt and output tokens of the requests finished
    in the traced window needed, over the traced window's length (from the
    trace) times the chip's peak FLOP/s, in %."""
    tr = d.get("trace")
    if d.get("kind") != "serve" or tr is None or not d.get("useful_flops_in_trace"):
        return None
    return 100.0 * d["useful_flops_in_trace"] / (tr.window_s * d["peak"]["flops"])


def idle_share(d):
    """Share of the traced window in which no operation ran on the device, in %."""
    tr = d.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share
