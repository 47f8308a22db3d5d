"""Median milliseconds a request waits in the service's queue, from its
admission to the start of the wave that runs it: ``queue_s`` of every
``done`` event in the traced window."""

import statistics


def read(ctx):
    events = ctx["obs_events"]
    if not events:
        return None
    waits = [e["queue_s"] for e in events
             if e.get("kind") == "done" and "queue_s" in e]
    if not waits:
        return None
    return statistics.median(waits) * 1e3
