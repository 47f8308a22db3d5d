"""The ``boats.fixed`` cell, checked on the CPU: its configuration, traffic
and checks resolve by name, Boats' byte floor at the v5e's bandwidth, and
the ``als.input_passes`` reader on synthetic event lists and on what a
small run of the program emits."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_harness as H  # noqa: E402
from bench_roofline import tucker_floor_s  # noqa: E402


def test_boats_fixed_cell_resolves():
    cell = H.load_cell("boats.fixed")
    assert cell.chips == 1
    assert cell.config["shape"] == [320, 240, 7000]
    assert cell.config["ranks"] == [10, 10, 10]
    assert cell.config["dtype"] == "float32"
    assert cell.traffic["loop"] == "closed"
    assert cell.traffic["pool"] == 2
    assert H.request_of(cell) == {"ranks": [10, 10, 10]}
    assert cell.checks == {"subspace": {"limit": 2e-4},
                           "core_resid": {"limit": 2.5e-4}}
    assert cell.end_to_end == ["decomp_ms", "setup_s"]
    assert {"als.input_passes", "device_idle.decomp",
            "sweep_roofline.decomp", "plan_ms"} <= set(cell.per_layer)


def test_boats_byte_floor_at_v5e_bandwidth():
    # one read of 320·240·7000 floats, one write of the 10³ core and the
    # 320·10 + 240·10 + 7000·10 factor entries, at 819 GB/s
    floor = tucker_floor_s((320, 240, 7000), (10, 10, 10), 819e9)
    assert floor * 1e3 == pytest.approx(2.626, abs=5e-4)


def _execute(sid, **attrs):
    return {"t": 0.0, "kind": "span", "name": "execute", "dur_s": 0.4,
            "span": sid, "parent": None, **attrs}


#: two decompositions of Boats with ALS on every mode, beside a plan span
WITH = [{"t": 0.0, "kind": "span", "name": "plan", "dur_s": 0.001,
         "span": 1, "parent": None, "methods": ["als"] * 3},
        _execute(2, als_passes=11.358), _execute(3, als_passes=11.358)]

#: the same window from a program whose spans carry no ``als_passes``
WITHOUT = [{k: v for k, v in e.items() if k != "als_passes"} for e in WITH]


@pytest.mark.parametrize("events, completed, expected", [
    (WITH, 2, 11.358),
    (WITH, 1, 2 * 11.358),
    (WITHOUT, 2, None),
    (WITH, 0, None),
    ([], 2, None),
    (None, 2, None),
])
def test_als_input_passes_reader(events, completed, expected):
    got = H.read_metric("als.input_passes",
                        {"obs_events": events, "completed": completed})
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_als_input_passes_on_the_program_s_own_events():
    repro = H.program()
    from repro import obs
    cfg = repro.core.TuckerConfig(ranks=(3, 3, 3),
                                  methods=("als", "als", "eig"))
    x = np.random.default_rng(0).standard_normal((12, 14, 16)).astype(
        np.float32)
    repro.core.decompose(x, cfg)
    with obs.capture() as buf:
        for _ in range(2):
            repro.core.decompose(x, cfg)
    got = H.read_metric("als.input_passes",
                        {"obs_events": buf.events(), "completed": 2})
    # mode 0 reads the whole input, mode 1 a quarter of it (12 → 3)
    assert got == pytest.approx(11 * (1 + 3 / 12))
