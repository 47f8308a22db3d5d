"""``CostModel.lane``: contraction widths priced as a matrix unit computes
them.  At ``lane=1`` every count is the plain Eq. 4/5 one; at the TPU's 128
a rank-10 contraction costs what a 128-wide one does, and a calibration
fitted on padded records gets its constants back."""

import json

import numpy as np
import pytest

from repro.core.cost_model import DEFAULT_COST_MODEL, CostModel, lane_for
from repro.core.solvers import (DEFAULT_ALS_ITERS, DEFAULT_OVERSAMPLE,
                                DEFAULT_POWER_ITERS)
from repro.tune.calibrate import fit_cost_model
from repro.tune.records import Measurement

GRID = [(i, r, j) for i in (7, 100, 320, 7000) for r in (1, 10, 20, 128)
        for j in (64, 400, 70_000) if r <= i]


def _eq4(i, r, j):
    return float(i) * i * j + 2.0 * i * r * j + 9.0 * float(i) ** 3


def _eq5(i, r, j, iters=DEFAULT_ALS_ITERS):
    qr = 2.0 * i * float(r) * r - (2.0 / 3.0) * float(r) ** 3
    return (4.0 * i * j * r + qr) * iters + 2.0 * i * j * r


def _rand(i, r, j):
    ell = min(i, r + DEFAULT_OVERSAMPLE)

    def qr(m, n):
        return 2.0 * m * float(n) * n - (2.0 / 3.0) * float(n) ** 3

    return (2.0 * i * ell * j + qr(i, ell)
            + DEFAULT_POWER_ITERS * (4.0 * i * ell * j + qr(i, ell))
            + 2.0 * i * ell * j
            + float(ell) * ell * j + 9.0 * float(ell) ** 3 + i * ell * r
            + 2.0 * ell * r * j)


@pytest.mark.parametrize("i, r, j", GRID)
def test_lane_one_gives_the_plain_counts(i, r, j):
    cm = CostModel(lane=1)
    assert cm.eig_flops(i, r, j) == pytest.approx(_eq4(i, r, j), rel=1e-12)
    assert cm.als_flops(i, r, j) == pytest.approx(_eq5(i, r, j), rel=1e-12)
    assert cm.rand_flops(i, r, j) == pytest.approx(_rand(i, r, j), rel=1e-12)
    assert cm.width(r) == r


def test_lane_128_prices_a_rank_10_ttm_as_128_wide():
    padded, wide = CostModel(lane=128), CostModel(lane=1)
    i, j = 320, 70_000
    # the TTM/TTT terms of ALS at rank 10 equal the plain count at rank 128
    # less the QR, which is no contraction over the input
    qr10, qr128 = wide.f_qr(i, 10), wide.f_qr(i, 128)
    iters = DEFAULT_ALS_ITERS
    assert padded.als_flops(i, 10, j) - iters * qr10 == pytest.approx(
        wide.als_flops(i, 128, j) - iters * qr128)
    # EIG: the Gram's 320 rounds to 384, the projection's 10 to 128
    assert padded.eig_flops(i, 10, j) == pytest.approx(
        i * 384.0 * j + 2.0 * i * 128 * j + wide.f_eig(i))
    # a width already on the lane is not padded
    assert padded.width(256) == 256 and padded.width(257) == 384


def test_lane_follows_the_platform():
    assert lane_for("tpu") == 128
    assert lane_for("cpu") == lane_for("gpu") == 1
    # with no calibration file for the pair, the selector prices the
    # textbook counts at the platform's lane
    from repro.core.selector import default_selector, load_calibration
    for platform, backend in [("tpu", "pallas"), ("gpu", "matfree")]:
        assert load_calibration(platform, backend) is None
        cm = default_selector(platform=platform, backend=backend).cost_model
        assert cm == DEFAULT_COST_MODEL.with_(lane=lane_for(platform))


def test_file_without_lane_loads_as_one(tmp_path):
    d = CostModel(lane=128, source="calibrated").to_dict()
    assert d["lane"] == 128
    assert CostModel.from_dict(d).lane == 128
    del d["lane"]
    assert CostModel.from_dict(d).lane == 1
    from repro.core.selector import model_dir
    for p in sorted(model_dir().glob("cost_cpu_*.json")):
        assert CostModel.from_dict(json.loads(p.read_text())).lane == 1


@pytest.mark.parametrize("platform, lane", [("tpu", 128), ("cpu", 1)])
def test_fit_recovers_constants_from_padded_records(platform, lane):
    truth = CostModel(c_eig=300.0, c_qr=1.0, eig_scale=4e-14,
                      als_scale=2.5e-14, eig_overhead_s=4e-4,
                      als_overhead_s=6e-4, lane=lane, source="calibrated")
    rng = np.random.default_rng(18)
    ms = []
    for _ in range(40):
        i = int(rng.integers(8, 2000))
        r = int(rng.integers(1, max(2, i // 4)))
        j = int(rng.integers(64, 1 << 20))
        for method in ("eig", "als"):
            ms.append(Measurement(platform=platform, backend="matfree",
                                  device="d", i_n=i, r_n=r, j_n=j,
                                  method=method,
                                  seconds=truth.predict_seconds(
                                      method, i, r, j)))
    cm = fit_cost_model(ms)
    assert cm is not None and cm.lane == lane
    assert cm.c_eig == pytest.approx(300.0, rel=1e-3)
    assert cm.eig_scale == pytest.approx(4e-14, rel=1e-3)
    assert cm.als_scale == pytest.approx(2.5e-14, rel=1e-3)
    assert cm.eig_overhead_s == pytest.approx(4e-4, rel=1e-3)
    assert cm.als_overhead_s == pytest.approx(6e-4, rel=1e-3)


def test_fit_refuses_records_from_two_platforms():
    ms = [Measurement(platform=platform, backend="matfree", device="d",
                      i_n=64, r_n=4, j_n=4096, method=method, seconds=1e-3)
          for platform in ("tpu", "cpu") for method in ("eig", "als")]
    with pytest.raises(ValueError, match="one platform"):
        fit_cost_model(ms)
