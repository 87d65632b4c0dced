"""Milliseconds in store puts and gets per finished workflow, from the
``repro_store_{put,get}_seconds`` histograms."""


def read(d):
    if d.get("kind") != "workflow" or not d["workflows"]:
        return None
    return 1e3 * d["store_s"] / d["workflows"]
