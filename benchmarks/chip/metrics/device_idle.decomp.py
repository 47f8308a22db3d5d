"""Share of the traced closed-loop window in which no op ran on the device."""
from bench_metrics import idle_pct


def read(ctx):
    return idle_pct(ctx)
