"""Arithmetic shared by the metric readers in ``metrics/``."""

from __future__ import annotations

import math

import numpy as np


def latency_percentile_ms(ctx, q: float):
    """The ``q``-th percentile of every request's latency, with a failed
    request at infinity; None where that lands on a failed request."""
    lat = ctx["latencies_s"]
    if not lat:
        return None
    with np.errstate(invalid="ignore"):
        v = float(np.percentile(np.asarray(lat, float), q))
    return v * 1e3 if math.isfinite(v) else None


def idle_pct(ctx):
    """Share of the traced window with no op on the device, in percent."""
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0 or not tr.ops:
        return None
    return 100.0 * tr.idle_share()
