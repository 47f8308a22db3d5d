"""The ``sketch.syncs`` reader, checked on the CPU: on synthetic event
lists (with ``syncs`` on the ``sketch`` spans, without, and with no
decomposition completed) and on what a small run of the program emits."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_harness as H  # noqa: E402


def _sketch(mode, widths, sid, **attrs):
    return {"t": 0.0, "kind": "span", "name": "sketch", "dur_s": 0.01,
            "span": sid, "parent": None, "mode": mode, "widths": widths,
            **attrs}


#: two decompositions of a 3-mode tensor: the first widens on mode 0
WITH = [_sketch(0, 2, 1, syncs=2), _sketch(1, 1, 2, syncs=1),
        _sketch(2, 1, 3, syncs=1),
        _sketch(0, 1, 4, syncs=1), _sketch(1, 1, 5, syncs=1),
        _sketch(2, 1, 6, syncs=1)]

#: the same window from a program whose spans carry no ``syncs``
WITHOUT = [{k: v for k, v in e.items() if k != "syncs"} for e in WITH]


def _read(events, completed=2):
    return H.read_metric("sketch.syncs", {"obs_events": events,
                                          "completed": completed})


@pytest.mark.parametrize("events, completed, expected", [
    (WITH, 2, 3.5),
    (WITHOUT, 2, None),
    (WITH, 0, None),
    ([], 2, None),
    (None, 2, None),
])
def test_sketch_syncs_reader(events, completed, expected):
    got = _read(events, completed)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_sketch_syncs_on_the_program_s_own_events():
    repro = H.program()
    from repro import obs
    cfg = repro.core.TuckerConfig(error_target=0.3)
    x = np.random.default_rng(0).standard_normal((12, 14, 16)).astype(
        np.float32)
    repro.core.decompose(x, cfg)
    with obs.capture() as buf:
        for _ in range(2):
            repro.core.decompose(x, cfg)
    events = buf.events()
    widths = sum(e["widths"] for e in obs.iter_spans(events)
                 if e["name"] == "sketch")
    assert _read(events) == pytest.approx(widths / 2)
    assert _read(events) >= 3      # one read at least per mode
