"""The per-layer metrics that read the program's own spans, checked on the
CPU: the five readers on synthetic event lists (and on an event stream
with none of their spans, as a program without them gives), the same
readers on what a small run of the program emits, and the program's
spans on the profiler's host plane, where the trace reduction can name
idle gaps by them."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_harness as H  # noqa: E402
import bench_trace as BT  # noqa: E402


def _span(name, dur_s, sid, parent=None, **attrs):
    return {"t": 0.0, "kind": "span", "name": name, "dur_s": dur_s,
            "span": sid, "parent": parent, **attrs}


#: a closed loop of two decompositions: each a front-door plan (holding a
#: DP search) and an execute whose sketch pass reads back twice and
#: re-plans at the resolved ranks; one plan nested in a plan counts once
CLOSED = [
    _span("plan.dp_search", 0.001, 2, parent=1),
    _span("plan", 0.004, 1),
    _span("sketch.readback", 0.002, 5, parent=4),
    _span("sketch.readback", 0.003, 6, parent=4),
    _span("sketch", 0.010, 4, parent=3),
    _span("plan", 0.002, 7, parent=3, refine=True),
    _span("execute", 0.030, 3),
    _span("plan", 0.001, 9, parent=8),
    _span("plan", 0.006, 8),
    _span("sketch.readback", 0.005, 11, parent=10),
    _span("sketch", 0.008, 10, parent=12),
    _span("execute", 0.020, 12),
]

#: a served window: three submits, two waves, their done events
SERVED = [
    _span("serve.validate", 0.001, 2, parent=1, rid=0),
    _span("serve.submit", 0.002, 1, rid=0),
    _span("serve.submit", 0.004, 3, rid=1),
    _span("serve.submit", 0.006, 4, rid=2),
    _span("serve.wave.dispatch", 0.010, 6, parent=5, rids=[0, 1]),
    _span("serve.wave", 0.011, 5, rids=[0, 1]),
    _span("serve.wave.finish", 0.020, 7, rids=[0, 1]),
    _span("serve.wave.dispatch", 0.030, 9, parent=8, rids=[2]),
    _span("serve.wave", 0.031, 8, rids=[2]),
    {"t": 0.0, "kind": "done", "rid": 0, "queue_s": 0.001},
    {"t": 0.0, "kind": "done", "rid": 1, "queue_s": 0.005},
    {"t": 0.0, "kind": "done", "rid": 2, "queue_s": 0.002},
    {"t": 0.0, "kind": "wave", "lanes": 2, "filled": 2, "wall_s": 0.03},
]

#: what a program without the new spans and stamps emits
WITHOUT = [
    {"t": 0.0, "kind": "span", "name": "execute", "dur_s": 0.03},
    {"t": 0.0, "kind": "span", "name": "sketch", "dur_s": 0.01},
    {"t": 0.0, "kind": "submit", "rid": 0},
    {"t": 0.0, "kind": "done", "rid": 0, "latency_s": 0.01},
]


def _read(name, events, completed=2):
    return H.read_metric(name, {"obs_events": events,
                                "completed": completed})


@pytest.mark.parametrize("name, events, expected", [
    # (4 + 2 + 6) ms of outermost plans over 2 decompositions
    ("plan_ms", CLOSED, 6.0),
    ("sketch.readback_ms", CLOSED, 5.0),
    ("serve.admit_ms", SERVED, 4.0),
    ("serve.queue_wait_ms", SERVED, 2.0),
    ("serve.dispatch_ms", SERVED, 20.0),
    ("plan_ms", WITHOUT, None),
    ("sketch.readback_ms", WITHOUT, None),
    ("serve.admit_ms", WITHOUT, None),
    ("serve.queue_wait_ms", WITHOUT, None),
    ("serve.dispatch_ms", WITHOUT, None),
    ("plan_ms", None, None),
    ("serve.queue_wait_ms", [], None),
])
def test_span_readers_on_synthetic_events(name, events, expected):
    got = _read(name, events)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_per_decomposition_readers_need_completions():
    assert _read("plan_ms", CLOSED, completed=0) is None
    assert _read("sketch.readback_ms", CLOSED, completed=0) is None


def _tensor(shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def test_readers_on_the_program_s_own_events():
    repro = H.program()
    from repro import obs
    cfg = repro.core.TuckerConfig(error_target=0.3)
    x = _tensor((12, 14, 16))
    repro.core.decompose(x, cfg)
    with obs.capture() as buf:
        for _ in range(2):
            repro.core.decompose(x, cfg)
    closed = buf.events()
    plan = _read("plan_ms", closed)
    readback = _read("sketch.readback_ms", closed)
    sketch = _read("sketch_ms", closed)
    assert plan > 0 and 0 < readback <= sketch

    svc = repro.serve.TuckerService()
    fixed = repro.core.TuckerConfig(ranks=(3, 3, 3))
    with obs.capture() as buf:
        tickets = [svc.submit(_tensor((10, 12, 8 + k), seed=k), fixed)
                   for k in range(3)]
        svc.drain()
        for t in tickets:
            svc.wait(t)
    svc.close()
    served = buf.events()
    assert _read("serve.admit_ms", served) > 0
    assert _read("serve.dispatch_ms", served) > 0
    assert _read("serve.queue_wait_ms", served) >= 0


def test_program_spans_land_on_the_profiler_host_plane(tmp_path):
    """Inside a profiler trace, the program's spans are host events on the
    device trace's clock: the trace reduction sees the adaptive pass's
    ``plan``, ``execute``, ``sketch`` and ``sketch.readback``, each
    readback inside a sketch."""
    import jax
    repro = H.program()
    from repro import obs
    cfg = repro.core.TuckerConfig(error_target=0.3)
    x = _tensor((12, 14, 16))
    repro.core.decompose(x, cfg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.capture() as buf, jax.profiler.TraceAnnotation(BT.WINDOW):
            res = repro.core.decompose(x, cfg)
            jax.block_until_ready(res.tucker.core)
    finally:
        jax.profiler.stop_trace()
    tr = BT.read_xplane(str(tmp_path))
    by_name: dict[str, list] = {}
    for s, e, name in tr.host:
        by_name.setdefault(name, []).append((s, e))
    assert {"plan", "execute", "sketch", "sketch.readback"} <= by_name.keys()
    sketches = by_name["sketch"]
    for s, e in by_name["sketch.readback"]:
        assert any(s0 <= s and e <= e0 for s0, e0 in sketches)
    # as many profiler events as bus spans of each name
    spans = [e["name"] for e in obs.iter_spans(buf.events())]
    for name in ("sketch", "sketch.readback", "plan", "execute"):
        assert len(by_name[name]) == spans.count(name)
