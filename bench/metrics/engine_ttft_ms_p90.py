"""p90 of the engine's time to first token over every request of the window:
``GenStats.first_token_s``, from entry to ``generate()`` until the first new
token is known, in ms.  Queueing before the call is not in it."""
from harness import percentile


def read(d):
    if d.get("kind") != "serve":
        return None
    ttft = [getattr(st, "first_token_s", None) for st in d["stats"]]
    if not ttft or None in ttft:  # an engine that does not time its first token
        return None
    return 1e3 * percentile(ttft, 90)
