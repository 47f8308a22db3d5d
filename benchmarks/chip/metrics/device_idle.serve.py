"""Share of the traced open-loop window in which no op ran on the device."""
from bench_metrics import idle_pct


def read(ctx):
    return idle_pct(ctx)
