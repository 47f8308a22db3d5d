"""Full reads of the input per decomposition that the program's ALS solver
makes: the ``als_passes`` attribute of its ``execute`` spans (each ALS
step's reads of its own input, weighted by that input's elements over the
decomposition's), summed over the traced window.  None where no
``execute`` span carries ``als_passes``, as a program that does not count
them gives."""


def read(ctx):
    events = ctx["obs_events"]
    if not events or not ctx["completed"]:
        return None
    passes = [e["als_passes"] for e in events
              if e.get("kind") == "span" and e.get("name") == "execute"
              and "als_passes" in e]
    if not passes:
        return None
    return sum(passes) / ctx["completed"]
