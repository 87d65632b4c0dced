"""Device-busy milliseconds in the traced window per workflow finished in it."""


def read(d):
    tr = d.get("trace")
    if d.get("kind") != "workflow" or tr is None or not d["workflows_in_trace"]:
        return None
    return 1e3 * tr.busy_s / d["workflows_in_trace"]
