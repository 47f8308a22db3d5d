"""Host milliseconds per decomposition in the program's rank-adaptive
sketch pass: its own ``sketch`` spans, summed over the traced window."""


def read(ctx):
    events = ctx["obs_events"]
    if not events or not ctx["completed"]:
        return None
    spans = [e["dur_s"] for e in events
             if e.get("kind") == "span" and e.get("name") == "sketch"]
    if not spans:
        return None
    return sum(spans) / ctx["completed"] * 1e3
