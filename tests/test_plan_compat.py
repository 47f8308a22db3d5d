"""Plan-JSON compatibility: fixed-rank plans written before the rank-policy
axis existed (PR 7 fixtures, checked in under ``tests/data/``) must load
unchanged, describe identically, and fresh fixed-rank plans must serialize
without any adaptive keys — rank-adaptive fields are strictly additive."""

import json
import re
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.core import TuckerConfig, TuckerPlan, plan
from repro.core.cost_model import als_flops
from repro.core.plan import _step_peak_bytes
from repro.core.selector import default_selector

DATA = Path(__file__).parent / "data"
FIXTURE_JSON = DATA / "plan_pr7_fixed_rank.json"
FIXTURE_DESCRIBE = DATA / "plan_pr7_describe.txt"

# the exact config the fixture was generated from (pre-rank-policy code)
FIXTURE_CFG = TuckerConfig(ranks=(40, 8, 12), methods=("eig", "als", "eig"),
                           mode_order="opt", donate_input=False)
FIXTURE_SHAPE = (48, 224, 128)
#: the fields of an ALS step that the cost model, not the schedule, sets
ALS_MODELED = ("flops", "peak_bytes", "predicted_s")


class TestLegacyPlanLoads:
    def test_fixture_loads_and_describes_identically(self):
        p = TuckerPlan.load(FIXTURE_JSON)
        assert p.shape == FIXTURE_SHAPE
        assert not p.is_adaptive
        assert p.config.error_target is None
        assert p.describe() == FIXTURE_DESCRIBE.read_text().rstrip("\n")

    def test_fixture_round_trips_byte_identically(self):
        p = TuckerPlan.load(FIXTURE_JSON)
        assert json.loads(p.to_json()) == json.loads(FIXTURE_JSON.read_text())

    def test_fresh_plan_matches_pre_rank_policy_serialization(self):
        # a plan built TODAY from the fixture's config serializes to the
        # same document the pre-PR-8 code wrote, except the ALS step's
        # modeled cost: ALS now orthonormalizes every iteration and closes
        # with a projection, so its flops, peak and prediction follow the
        # current cost model (asserted against it here)
        p = plan(FIXTURE_SHAPE, jnp.float32, FIXTURE_CFG)
        fresh, fixture = json.loads(p.to_json()), json.loads(
            FIXTURE_JSON.read_text())
        fresh.pop("select_seconds"), fixture.pop("select_seconds")
        k = [s["method"] for s in fixture["schedule"]].index("als")
        als = dict(fresh["schedule"][k])
        for doc in (fresh, fixture):
            for key in ALS_MODELED:
                doc["schedule"][k].pop(key)
        assert fresh == fixture
        dims = (als["i_n"], als["r_n"], als["j_n"])
        cost_model = default_selector(backend="matfree").cost_model
        assert als["flops"] == pytest.approx(
            als_flops(*dims, FIXTURE_CFG.als_iters))
        assert als["peak_bytes"] == _step_peak_bytes("als", *dims, 4)
        assert als["predicted_s"] == pytest.approx(
            cost_model.predict_seconds("als", *dims, FIXTURE_CFG.als_iters))

        lines = FIXTURE_DESCRIBE.read_text().rstrip("\n").split("\n")
        got = p.describe().split("\n")
        als_line, total_line = 2 + k, len(lines) - 1
        assert len(got) == len(lines)
        for n, (a, b) in enumerate(zip(got, lines)):
            if n not in (als_line, total_line):
                assert a == b
        assert got[als_line] == re.sub(
            r"flops=\S+  peak=\S+B  pred=\S+ms",
            f"flops={als['flops']:.3g}  peak={als['peak_bytes']:,}B  "
            f"pred={als['predicted_s'] * 1e3:.3f}ms", lines[als_line])
        assert got[total_line] == (
            f"  total: flops={p.total_flops:.3g}  peak={p.peak_bytes:,}B  "
            f"predicted={p.total_predicted_s * 1e3:.3f}ms")

    def test_fixture_plan_still_executes(self):
        import numpy as np
        p = TuckerPlan.load(FIXTURE_JSON)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(p.shape),
                        jnp.float32)
        res = p.execute(x)
        assert res.tucker.ranks == (40, 8, 12)


class TestNoAdaptiveKeysOnFixedPlans:
    def test_config_dict_has_no_adaptive_keys(self):
        d = FIXTURE_CFG.to_dict()
        for key in ("error_target", "rank_grid", "oversample", "power_iters"):
            assert key not in d, key

    def test_plan_json_steps_have_no_adaptive_keys(self):
        doc = json.loads(plan(FIXTURE_SHAPE, jnp.float32,
                              FIXTURE_CFG).to_json())
        for key in ("error_target", "rank_grid", "oversample", "power_iters"):
            assert key not in doc["config"], key
        for step in doc["schedule"]:
            assert "rank_grid" not in step
            assert "tau" not in step
