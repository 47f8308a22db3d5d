"""Milliseconds per decomposition: the whole closed-loop window (host
clock) over the decompositions completed in it."""


def read(ctx):
    if not ctx["completed"]:
        return None
    return ctx["window_s"] / ctx["completed"] * 1e3
