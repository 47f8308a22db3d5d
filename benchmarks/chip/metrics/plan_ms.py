"""Host milliseconds per decomposition in planning: the program's
outermost ``plan`` spans (the front door's, and the re-plan at the
resolved ranks that a rank-adaptive call makes inside ``execute``; a
``plan`` nested in another ``plan`` counts once), summed over the traced
window."""


def read(ctx):
    events = ctx["obs_events"]
    if not events or not ctx["completed"]:
        return None
    spans = {e["span"]: e for e in events
             if e.get("kind") == "span" and "span" in e}

    def under_plan(e):
        seen, p = set(), e.get("parent")
        while p in spans and p not in seen:
            seen.add(p)
            if spans[p].get("name") == "plan":
                return True
            p = spans[p].get("parent")
        return False

    durs = [e["dur_s"] for e in spans.values()
            if e.get("name") == "plan" and not under_plan(e)]
    if not durs:
        return None
    return sum(durs) / ctx["completed"] * 1e3
