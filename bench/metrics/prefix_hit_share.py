"""Share of prompt chunks restored from a KV snapshot instead of prefilled:
``repro_serve_chunks_skipped_total`` over ``repro_serve_chunks_total``."""


def read(d):
    if d.get("kind") != "serve" or not d["counters"]["repro_serve_chunks_total"]:
        return None
    c = d["counters"]
    return 100.0 * c["repro_serve_chunks_skipped_total"] / c["repro_serve_chunks_total"]
