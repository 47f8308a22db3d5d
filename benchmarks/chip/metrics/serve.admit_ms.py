"""Mean host milliseconds a caller spends inside the service's ``submit``:
its ``serve.submit`` spans over the traced window (the finiteness check
and the locked enqueue are inside)."""


def read(ctx):
    events = ctx["obs_events"]
    if not events:
        return None
    durs = [e["dur_s"] for e in events
            if e.get("kind") == "span" and e.get("name") == "serve.submit"]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e3
