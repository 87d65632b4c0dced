"""Milliseconds of KV snapshot saving and loading per request, from the
``repro_serve_snapshot_{save,load}_seconds`` histograms.  It reads 0 when no
snapshot moved, which is a reading and not a missing one."""


def read(d):
    if d.get("kind") != "serve" or not d["counters"]["repro_serve_requests_total"]:
        return None
    c = d["counters"]
    io_s = c["repro_serve_snapshot_save_seconds"] + c["repro_serve_snapshot_load_seconds"]
    return 1e3 * io_s / c["repro_serve_requests_total"]
