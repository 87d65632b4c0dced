"""Share of the routed token-to-expert assignments of the window that went
to the experts this chip holds: ``repro_serve_expert_held_assignments_total``
over ``repro_serve_expert_assignments_total``, in %."""


def read(d):
    c = d.get("counters", {})
    total = c.get("repro_serve_expert_assignments_total")
    if d.get("kind") != "serve" or not total:
        return None
    return 100.0 * c.get("repro_serve_expert_held_assignments_total", 0.0) / total
