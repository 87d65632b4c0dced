"""Latent attention's share of its roofline in the prefill chunks of the
traced window: the least time of the work from the cached latents to each
head's context (``dsv2.attention_core``, the cheaper of the decompressed and
absorbed forms, every layer), over the device time of the operations in the
``mla.attend`` scope of ``jit_prefill_chunk``."""


def read(d):
    t = d.get("scope_s", {}).get("jit_prefill_chunk", {}).get("mla.attend")
    if not t or not d.get("mla_prefill_work") or d.get("peak") is None:
        return None
    return 100.0 * sum(w.seconds(d["peak"]) for w in d["mla_prefill_work"]) / t
