"""Share of modules not run because a stored result was reused: the sum of
``n_skipped`` over the sum of modules, every finished workflow of the window."""


def read(d):
    if d.get("kind") != "workflow" or not d["modules"]:
        return None
    return 100.0 * d["skipped"] / d["modules"]
