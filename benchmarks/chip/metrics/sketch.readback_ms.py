"""Host milliseconds per decomposition that the rank-adaptive sketch pass
spends waiting on the device: its ``sketch.readback`` spans (the
eigenvalue and energy reads of each sketch width tried, and the wait for
each mode's shrunk tensor), summed over the traced window."""


def read(ctx):
    events = ctx["obs_events"]
    if not events or not ctx["completed"]:
        return None
    durs = [e["dur_s"] for e in events
            if e.get("kind") == "span" and e.get("name") == "sketch.readback"]
    if not durs:
        return None
    return sum(durs) / ctx["completed"] * 1e3
