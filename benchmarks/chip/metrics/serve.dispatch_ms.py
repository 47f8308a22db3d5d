"""Mean host milliseconds the service spends putting one wave on the
device (stacking, padding, slot copies and the enqueue of its programs):
its ``serve.wave.dispatch`` spans over the traced window."""


def read(ctx):
    events = ctx["obs_events"]
    if not events:
        return None
    durs = [e["dur_s"] for e in events
            if e.get("kind") == "span"
            and e.get("name") == "serve.wave.dispatch"]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e3
