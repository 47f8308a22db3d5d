"""Requests per wave: the service's filled lanes over its waves, both as
deltas of its bucket counters across the window."""


def read(ctx):
    c = ctx["counters"]
    if not c or not c.get("waves"):
        return None
    return c["lanes_filled"] / c["waves"]
