"""The held experts' grouped matmuls' share of their roofline over the traced
window: the least time of each step call's routed work (``dsv2.routed_work``
from the call's own assignment counts: a held expert's weights read once
where some token reached it), over the device time of the operations in the
``moe.experts`` scope of both step programs."""


def read(d):
    scopes = d.get("scope_s", {})
    t = sum(scopes.get(p, {}).get("moe.experts", 0.0)
            for p in ("jit_prefill_chunk", "jit_decode_step"))
    if not t or not d.get("expert_work") or d.get("peak") is None:
        return None
    return 100.0 * sum(w.seconds(d["peak"]) for w in d["expert_work"]) / t
