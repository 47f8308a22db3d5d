"""The benchmark's arithmetic, checked on the CPU: the trace reduction on
small synthetic traces, the byte floor against a hand count, the metric
readers, the peak table, and the load generator's seeding."""

from __future__ import annotations

import math
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_harness as H  # noqa: E402
import bench_trace as BT  # noqa: E402
from bench_roofline import peaks_for, tucker_floor_s  # noqa: E402

MS = 1_000_000  # ns per ms


def test_union_counts_overlap_once_and_clips_to_the_window():
    ivs = [(0, 4 * MS), (2 * MS, 6 * MS), (10 * MS, 12 * MS), (11 * MS, 11 * MS),
           (-5 * MS, 1 * MS), (19 * MS, 30 * MS)]
    # window [0, 20 ms]: busy [0,6] + [10,12] + [19,20] = 9 ms
    assert BT.busy_ns(ivs, 0, 20 * MS) == 9 * MS
    assert BT.gaps(ivs, 0, 20 * MS) == [(6 * MS, 10 * MS), (12 * MS, 19 * MS)]
    assert BT.merge([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4), (5, 9)]


def _trace():
    # two devices over a 100 ms window; device 0 busy 60 ms, device 1 40 ms
    ops = {
        "/device:TPU:0": [(0, 30 * MS, "m:a"), (10 * MS, 20 * MS, "m:b"),
                          (50 * MS, 80 * MS, "m:a")],
        "/device:TPU:1": [(0, 40 * MS, "m:c")],
    }
    host = [(0, 100 * MS, BT.WINDOW), (28 * MS, 52 * MS, "outer"),
            (35 * MS, 45 * MS, "np.asarray"), (79 * MS, 101 * MS, "sleep")]
    return BT.DeviceTrace(window_ns=(0, 100 * MS), ops=ops, host=host)


def test_busy_idle_and_breakdown_of_a_synthetic_trace():
    tr = _trace()
    assert tr.window_s == pytest.approx(0.1)
    assert tr.busy_s() == pytest.approx(0.05)       # mean of 60 and 40 ms
    assert tr.idle_share() == pytest.approx(0.5)
    top = dict(tr.top_ops())
    assert top["m:a"] == pytest.approx(0.03)        # 60 ms over 2 devices
    assert top["m:c"] == pytest.approx(0.02)
    # device 0 idles [30, 50] and [80, 100]; the innermost host event at
    # each midpoint names the gap
    assert tr.idle_gaps() == [["np.asarray", pytest.approx(0.02)],
                              ["sleep", pytest.approx(0.02)]]


def test_ops_are_named_by_their_module():
    named = BT._module_of([(5, 6, "%fusion.1"), (25, 26, "%copy.2"),
                           (40, 41, "%x")],
                          [(0, 10, "jit_a"), (20, 30, "jit_b")])
    assert [n for *_, n in named] == ["jit_a:%fusion.1", "jit_b:%copy.2",
                                     "?:%x"]


def test_byte_floor_matches_the_hand_count():
    bw = peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert bw == 819e9
    # Boats: 320*240*7000 floats in, 10^3 core + (320+240+7000)*10 out
    boats = (320 * 240 * 7000 + 10 ** 3 + (320 + 240 + 7000) * 10) * 4 / bw
    assert tucker_floor_s((320, 240, 7000), (10, 10, 10), bw) == boats
    assert boats * 1e3 == pytest.approx(2.626, abs=5e-4)
    cavity = tucker_floor_s((100, 100, 10000), (20, 20, 20), bw)
    assert cavity * 1e3 == pytest.approx(0.489, abs=5e-4)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v99")


def _read(name, **ctx):
    base = {"trace": None, "counters": None, "calls": [], "completed": 0,
            "latencies_s": [], "obs_events": None, "window_s": 1.0}
    base.update(ctx)
    return H.read_metric(name, base)


def test_pad_waste_and_requests_per_wave_read_the_service_counters():
    repro = H.program()
    svc = repro.serve.TuckerService()
    before = H.service_counters(svc)
    x = np.random.default_rng(0).standard_normal((100, 100, 16)).astype(
        np.float32)
    t = svc.submit(x, repro.core.TuckerConfig(ranks=(2, 2, 2)))
    svc.drain()
    svc.wait(t)
    after = H.service_counters(svc)
    svc.close()
    delta = {k: after[k] - before.get(k, 0) for k in after}
    # 100x100x16 pads to the grid-8 bucket 104x104x16
    assert _read("serve.pad_waste", counters=delta) == pytest.approx(
        100 * (1 - 100 ** 2 / 104 ** 2))
    assert round(_read("serve.pad_waste", counters=delta), 1) == 7.5
    assert _read("serve.reqs_per_wave", counters=delta) == 1.0
    assert _read("serve.pad_waste", counters={}) is None


def test_roofline_and_idle_readers_on_a_synthetic_trace():
    tr = _trace()
    peaks = {"hbm_bytes_per_s": 819e9}
    calls = [{"shape": (100, 100, 10000), "ranks": (20, 20, 20)}] * 10
    v = _read("sweep_roofline.decomp", trace=tr, calls=calls, peaks=peaks)
    assert v == pytest.approx(100 * 10 * tucker_floor_s(
        (100, 100, 10000), (20, 20, 20), 819e9) / 0.05)
    assert _read("device_idle.decomp", trace=tr) == pytest.approx(50.0)
    assert _read("device_idle.serve", trace=tr) == pytest.approx(50.0)
    # no trace, nothing to read: the metric is left out, never 0
    assert _read("sweep_roofline.decomp", calls=calls, peaks=peaks) is None
    assert _read("device_idle.decomp") is None


def test_latency_and_rate_readers():
    lat = [0.010] * 90 + [0.100] * 10
    assert _read("serve_p50_ms", latencies_s=lat) == pytest.approx(10.0)
    assert _read("serve_p95_ms", latencies_s=lat) == pytest.approx(100.0)
    # a failed request is never ready: a tail that lands on one is unknown
    assert _read("serve_p95_ms", latencies_s=lat[:90] + [math.inf] * 10) \
        is None
    assert _read("decomp_ms", window_s=2.0, completed=40) == 50.0
    assert _read("sketch_ms", completed=2, obs_events=[
        {"kind": "span", "name": "sketch", "dur_s": 0.01},
        {"kind": "span", "name": "execute", "dur_s": 1.0},
        {"kind": "span", "name": "sketch", "dur_s": 0.03}]) == \
        pytest.approx(20.0)


def test_every_seed_offers_the_same_work_in_another_order():
    traffic = {"rate_per_s": 50.0, "pool_per_extent": 2,
               "extents": {"axis": 2, "values": [10, 20, 50, 100],
                           "weights": [0.5, 0.25, 0.15, 0.10]}}
    a = H.arrival_schedule(traffic, 2 ** 31 + 17, 4.0)
    b = H.arrival_schedule(traffic, 2 ** 40 + 3, 4.0)
    assert len(a) == len(b) == 200
    assert Counter(v for _, (v, _) in a) == Counter(v for _, (v, _) in b) \
        == {10: 100, 20: 50, 50: 30, 100: 20}
    assert a != b
    assert a[0][0] == 0.0 and a[-1][0] < 4.0
    assert H.arrival_schedule(traffic, 2 ** 31 + 17, 4.0) == a


def test_seeds_keep_all_their_bits():
    from bench_data import key_for
    import jax
    keys = {tuple(np.asarray(jax.random.key_data(key_for(s))).tolist())
            for s in (0, 2 ** 32, 2 ** 40, 2 ** 31 + 5)}
    assert len(keys) == 4
