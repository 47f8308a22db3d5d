"""95th-percentile latency over every request due in the window, from its
due time to its result being ready; a failed request counts as never
ready."""
from bench_metrics import latency_percentile_ms


def read(ctx):
    return latency_percentile_ms(ctx, 95)
