"""Padding slack: 1 - true elements over slot elements of the requests the
service completed, as deltas of its bucket counters across the window."""


def read(ctx):
    c = ctx["counters"]
    if not c or not c.get("slot_elems"):
        return None
    return 100.0 * (1.0 - c["true_elems"] / c["slot_elems"])
