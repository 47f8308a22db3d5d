"""Blocking device-to-host reads per decomposition in the rank-adaptive
sketch pass: the ``syncs`` attribute of its ``sketch`` spans (the reads
each mode's rank decision made), summed over the traced window.  None
where no ``sketch`` span carries ``syncs``, as a program that does not
count its reads gives."""


def read(ctx):
    events = ctx["obs_events"]
    if not events or not ctx["completed"]:
        return None
    syncs = [e["syncs"] for e in events
             if e.get("kind") == "span" and e.get("name") == "sketch"
             and "syncs" in e]
    if not syncs:
        return None
    return sum(syncs) / ctx["completed"]
