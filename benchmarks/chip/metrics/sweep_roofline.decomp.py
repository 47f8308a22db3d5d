"""Memory-roofline share of a decomposition (bound: memory): the byte floor
of every decomposition completed in the traced window (one read of the
input, one write of core and factors, at the peak HBM bandwidth) over the
device's busy time in that window."""
from bench_roofline import tucker_floor_s


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["calls"]:
        return None
    busy = tr.busy_s()
    if busy <= 0:
        return None
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    floor = sum(tucker_floor_s(c["shape"], c["ranks"], bw)
                for c in ctx["calls"])
    return 100.0 * floor / busy
