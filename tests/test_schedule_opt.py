"""Schedule search (subset DP), memory caps, and donated sweeps.

Covers the plan-time optimizer end to end: DP-vs-brute-force exactness over
all N! orders (with per-step solver choice), cap feasibility agreement and
the binding-step error, plan JSON roundtrips of the new config fields,
donated-sweep bitwise parity + the measured live-array high-water win, and
the runtime cap smoke used by the tier-2 CI job.
"""

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DEFAULT_COST_MODEL,
    MemoryCapError,
    TuckerConfig,
    TuckerPlan,
    optimize_schedule,
    plan,
    resolve_schedule,
    sthosvd,
)
from repro.core.api import donation_supported
from repro.core.plan import _step_peak_bytes, resolve_mode_order
from repro.core.schedule_opt import SEARCH_METHODS, step_cost


def lowrank(dims, ranks, seed=0, noise=0.01):
    rng = np.random.default_rng(seed)
    from repro.core import tensor_ops as T
    core = rng.standard_normal(ranks)
    us = [np.linalg.qr(rng.standard_normal((d, r)))[0]
          for d, r in zip(dims, ranks)]
    x = T.reconstruct(jnp.asarray(core, jnp.float32),
                      [jnp.asarray(u, jnp.float32) for u in us])
    rms = float(jnp.sqrt(jnp.mean(x ** 2)))
    return x + noise * rms * jnp.asarray(rng.standard_normal(dims), jnp.float32)


def brute_force(shape, ranks, *, methods=None, als_iters=5, itemsize=4,
                cap=None, cm=DEFAULT_COST_MODEL):
    """Reference: enumerate every order x every per-step solver assignment."""
    n = len(shape)
    best = None
    for order in itertools.permutations(range(n)):
        cands = [([methods[m]] if methods is not None
                  else list(SEARCH_METHODS)) for m in order]
        for meths in itertools.product(*cands):
            cur, cost, ok = list(shape), 0.0, True
            for m, meth in zip(order, meths):
                i_n, r_n = cur[m], ranks[m]
                j_n = math.prod(cur) // i_n
                if cap is not None and \
                        _step_peak_bytes(meth, i_n, r_n, j_n, itemsize) > cap:
                    ok = False
                    break
                cost += step_cost(cm, meth, i_n, r_n, j_n, als_iters)
                cur[m] = r_n
            if ok and (best is None or cost < best[0]):
                best = (cost, order, meths)
    return best


# ---------------------------------------------------------------------------
# DP exactness vs brute force
# ---------------------------------------------------------------------------

class TestDPOptimality:
    @pytest.mark.parametrize("shape,ranks", [
        ((30, 8, 22), (3, 6, 4)),
        ((16, 16, 16), (4, 4, 4)),
        ((40, 6, 12, 9), (5, 4, 3, 2)),
    ])
    def test_equal_totals_auto_methods(self, shape, ranks):
        search = optimize_schedule(shape, ranks)
        ref = brute_force(shape, ranks)
        assert math.isclose(search.total_cost, ref[0], rel_tol=1e-9)

    def test_equal_totals_pinned_methods(self):
        shape, ranks = (24, 10, 18), (4, 5, 3)
        search = optimize_schedule(shape, ranks, methods=["eig"] * 3)
        ref = brute_force(shape, ranks, methods=["eig"] * 3)
        assert math.isclose(search.total_cost, ref[0], rel_tol=1e-9)
        assert search.methods == ("eig",) * 3

    def test_beats_or_matches_every_fixed_order(self):
        shape, ranks = (40, 6, 12, 9), (5, 4, 3, 2)
        search = optimize_schedule(shape, ranks, methods=["eig"] * 4)
        for order in itertools.permutations(range(4)):
            cur, cost = list(shape), 0.0
            for m in order:
                j_n = math.prod(cur) // cur[m]
                cost += step_cost(DEFAULT_COST_MODEL, "eig", shape[m],
                                  ranks[m], j_n, 5)
                cur[m] = ranks[m]
            assert search.total_cost <= cost + 1e-9 * cost

    @pytest.mark.parametrize("frac", [0.35, 0.6, 0.9])
    def test_cap_feasibility_agreement(self, frac):
        shape, ranks = (30, 8, 22), (3, 6, 4)
        # cap as a fraction of the worst single-step peak seen uncapped
        worst = max(_step_peak_bytes(m, shape[i], ranks[i],
                                     math.prod(shape) // shape[i], 4)
                    for i in range(3) for m in SEARCH_METHODS)
        cap = int(worst * frac)
        ref = brute_force(shape, ranks, cap=cap)
        if ref is None:
            with pytest.raises(MemoryCapError):
                optimize_schedule(shape, ranks, memory_cap_bytes=cap)
        else:
            search = optimize_schedule(shape, ranks, memory_cap_bytes=cap)
            assert math.isclose(search.total_cost, ref[0], rel_tol=1e-9)

    def test_cap_forces_smaller_solver(self):
        # uncapped, ALS wins mode 0 on FLOPs — but its R-tensor scratch
        # (2·R·J in fp32) outweighs EIG's I² Gram here, so a cap just below
        # ALS's peak forces the slower-but-smaller EIG on that step
        shape, ranks = (80, 64, 64), (4, 32, 32)
        free = resolve_schedule(shape, ranks, mode_order="opt",
                                cost_model=DEFAULT_COST_MODEL)
        worst = max(free, key=lambda s: s.peak_bytes)
        assert worst.method == "als"
        capped = resolve_schedule(shape, ranks, mode_order="opt",
                                  cost_model=DEFAULT_COST_MODEL,
                                  memory_cap_bytes=worst.peak_bytes - 1)
        flip = next(s for s in capped if s.mode == worst.mode)
        assert flip.method == "eig"
        assert flip.peak_bytes < worst.peak_bytes
        assert sum(s.flops for s in capped) > sum(s.flops for s in free)
        assert all(s.peak_bytes < worst.peak_bytes for s in capped)


# ---------------------------------------------------------------------------
# Infeasible caps fail at plan time, naming the binding step
# ---------------------------------------------------------------------------

class TestCapErrors:
    def test_opt_infeasible_names_binding_step(self):
        with pytest.raises(MemoryCapError) as e:
            optimize_schedule((96, 16, 64), (4, 12, 8),
                              memory_cap_bytes=1000)
        msg = str(e.value)
        assert "mode" in msg and "1,000" in msg and "bytes" in msg

    def test_fixed_order_schedule_checked_too(self):
        with pytest.raises(MemoryCapError) as e:
            resolve_schedule((96, 16, 64), (4, 12, 8), methods="eig",
                             memory_cap_bytes=1000)
        assert "step 0" in str(e.value) and "mode_order='opt'" in str(e.value)

    def test_plan_level_cap_error(self):
        cfg = TuckerConfig(ranks=(4, 12, 8), mode_order="opt",
                           memory_cap_bytes=1000)
        with pytest.raises(MemoryCapError):
            plan((96, 16, 64), jnp.float32, cfg)

    def test_sthosvd_entry_point_cap(self):
        x = lowrank((24, 20, 16), (3, 3, 3))
        with pytest.raises(MemoryCapError):
            sthosvd(x, (3, 3, 3), methods="eig", memory_cap_bytes=1000)

    def test_feasible_cap_respected_in_plan(self):
        # natural order's bottleneck (mode 0 barely compresses, so mode 1's
        # solve still sees a huge J) is avoidable by reordering: a cap below
        # it is infeasible for the natural order but fine for the DP
        shape, ranks = (16, 96, 64), (12, 4, 8)
        free = plan(shape, jnp.float32, TuckerConfig(ranks=ranks))
        cap = int(max(s.peak_bytes for s in free.schedule) * 0.8)
        p = plan(shape, jnp.float32,
                 TuckerConfig(ranks=ranks, mode_order="opt",
                              memory_cap_bytes=cap))
        assert all(s.peak_bytes <= cap for s in p.schedule)
        # and the plan executes correctly under the cap
        x = lowrank(shape, ranks)
        assert float(p.execute(x).tucker.rel_error(x)) < 0.05

    def test_config_validation(self):
        with pytest.raises(ValueError):
            TuckerConfig(ranks=(2, 2, 2), mode_order="fastest")
        with pytest.raises(ValueError):
            TuckerConfig(ranks=(2, 2, 2), memory_cap_bytes=0)
        with pytest.raises(ValueError):
            resolve_mode_order((4, 4, 4), (2, 2, 2), "opt")


# ---------------------------------------------------------------------------
# Plan integration: correctness, JSON roundtrip, modeled-cost ordering
# ---------------------------------------------------------------------------

class TestOptPlans:
    def test_opt_plan_executes_correctly(self):
        shape, ranks = (40, 12, 30), (4, 6, 5)
        x = lowrank(shape, ranks)
        p = plan(shape, jnp.float32,
                 TuckerConfig(ranks=ranks, mode_order="opt"))
        res = p.execute(x)
        assert float(res.tucker.rel_error(x)) < 0.05
        # the schedule visits every mode exactly once
        assert sorted(s.mode for s in p.schedule) == [0, 1, 2]

    def test_opt_never_worse_than_fixed_orders_modeled(self):
        shape, ranks = (96, 16, 64), (4, 12, 8)
        opt = resolve_schedule(shape, ranks, methods="eig",
                               mode_order="opt",
                               cost_model=DEFAULT_COST_MODEL)
        for order in ([0, 1, 2], [2, 0, 1], "shrink"):
            ref = resolve_schedule(shape, ranks, methods="eig",
                                   mode_order=order,
                                   cost_model=DEFAULT_COST_MODEL)
            assert sum(s.flops for s in opt) <= sum(s.flops for s in ref) * \
                (1 + 1e-9)

    def test_plan_json_roundtrip(self, tmp_path):
        cfg = TuckerConfig(ranks=(4, 6, 5), mode_order="opt",
                           memory_cap_bytes=10_000_000, donate_input=True)
        p = plan((40, 12, 30), jnp.float32, cfg)
        path = tmp_path / "plan.json"
        p.save(path)
        q = TuckerPlan.load(path)
        assert q.config.mode_order == "opt"
        assert q.config.memory_cap_bytes == 10_000_000
        assert q.config.donate_input is True
        assert [s.to_dict() for s in q.schedule] == \
            [s.to_dict() for s in p.schedule]
        # donate_input=True means execute CONSUMES its array — use a copy
        # per call (the override donate=False path is covered elsewhere)
        xn = np.asarray(lowrank((40, 12, 30), (4, 6, 5)))
        np.testing.assert_array_equal(
            np.asarray(p.execute(jnp.asarray(xn)).tucker.core),
            np.asarray(q.execute(jnp.asarray(xn)).tucker.core))

    def test_total_predicted_s_surfaced(self):
        p = plan((40, 12, 30), jnp.float32,
                 TuckerConfig(ranks=(4, 6, 5), mode_order="opt"))
        assert p.total_predicted_s == sum(s.predicted_s for s in p.schedule)
        assert "TuckerPlan" in p.describe() and "step 0" in p.describe()

    def test_trace_reports_predicted_vs_actual(self):
        x = lowrank((24, 20, 16), (3, 3, 3))
        res = sthosvd(x, (3, 3, 3), methods="eig")
        rep = res.report()
        assert "seconds" in rep and "total" in rep
        for t in res.trace:
            assert t.delta_s == t.seconds - t.predicted_s


# ---------------------------------------------------------------------------
# Donated sweeps
# ---------------------------------------------------------------------------

def _live_bytes():
    return sum(a.nbytes for a in jax.live_arrays())


class TestDonation:
    SHAPE, RANKS = (64, 48, 40), (6, 8, 5)

    def _plan(self, **kw):
        return plan(self.SHAPE, jnp.float32,
                    TuckerConfig(ranks=self.RANKS, methods="eig", **kw))

    def test_bitwise_parity_donated_vs_undonated(self):
        p = self._plan()
        xn = np.asarray(lowrank(self.SHAPE, self.RANKS))
        r0 = p.execute(jnp.asarray(xn), donate=False)
        r1 = p.execute(jnp.asarray(xn), donate=True)
        np.testing.assert_array_equal(np.asarray(r0.tucker.core),
                                      np.asarray(r1.tucker.core))
        for u0, u1 in zip(r0.tucker.factors, r1.tucker.factors):
            np.testing.assert_array_equal(np.asarray(u0), np.asarray(u1))

    def test_donated_input_is_consumed(self):
        p = self._plan()
        x = jnp.asarray(np.asarray(lowrank(self.SHAPE, self.RANKS)))
        res = p.execute(x, donate=True)
        jax.block_until_ready(res.tucker.core)
        assert x.is_deleted()

    def test_auto_policy_never_invalidates_caller_array(self):
        p = self._plan()   # donate_input=None (auto)
        x = jnp.asarray(np.asarray(lowrank(self.SHAPE, self.RANKS)))
        res = p.execute(x)
        jax.block_until_ready(res.tucker.core)
        assert not x.is_deleted()
        np.testing.assert_allclose(float(jnp.sum(x)), float(jnp.sum(x)))

    def test_auto_policy_donates_host_inputs(self):
        if not donation_supported(jax.default_backend()):
            pytest.skip("platform has no buffer donation")
        p = self._plan()
        xn = np.asarray(lowrank(self.SHAPE, self.RANKS))
        base = _live_bytes()
        res = p.execute(xn)          # device copy created AND donated inside
        jax.block_until_ready(res.tucker.core)
        held = _live_bytes() - base  # results only, no dead copy of X
        assert held < xn.nbytes

    def test_live_array_high_water_below_undonated(self):
        if not donation_supported(jax.default_backend()):
            pytest.skip("platform has no buffer donation")
        p = self._plan()
        xn = np.asarray(lowrank(self.SHAPE, self.RANKS))

        def high_water(donate):
            base = _live_bytes()
            x = jnp.asarray(xn)
            res = p.execute(x, donate=donate)
            jax.block_until_ready(res.tucker.core)
            hw = _live_bytes() - base
            del x, res
            return hw

        undonated, donated = high_water(False), high_water(True)
        assert donated < undonated
        assert undonated - donated == xn.nbytes

    def test_env_escape_hatch(self, monkeypatch):
        p = self._plan()
        monkeypatch.setenv("ATUCKER_NO_DONATE", "1")
        x = jnp.asarray(np.asarray(lowrank(self.SHAPE, self.RANKS)))
        res = p.execute(x, donate=True)
        jax.block_until_ready(res.tucker.core)
        assert not x.is_deleted()

    def test_config_false_wins_over_auto(self):
        p = self._plan(donate_input=False)
        assert p.donates is False
        # and the modeled peak charges the undonated input copy
        assert p.peak_bytes >= self._plan(donate_input=True).peak_bytes

    def test_interpret_mode_backend_never_donates(self):
        p = plan(self.SHAPE, jnp.float32,
                 TuckerConfig(ranks=self.RANKS, methods="eig",
                              impl="pallas", donate_input=True))
        if jax.default_backend() == "tpu":
            pytest.skip("pallas is native on TPU; guard targets interpret mode")
        assert p.donates is False


# ---------------------------------------------------------------------------
# Runtime cap smoke (the tier-2 CI job body)
# ---------------------------------------------------------------------------

class TestRuntimeCapSmoke:
    def test_capped_plan_high_water_stays_bounded(self):
        """Plan under a tight cap, execute eagerly step by step, and sample
        jax.live_arrays between steps: the extra footprint beyond the held
        input must stay within the cap the plan promised."""
        from repro.core.plan import solve_step

        shape, ranks = (20, 96, 56), (16, 4, 7)
        free = plan(shape, jnp.float32, TuckerConfig(ranks=ranks))
        cap = int(max(s.peak_bytes for s in free.schedule) * 0.8)
        p = plan(shape, jnp.float32,
                 TuckerConfig(ranks=ranks, mode_order="opt",
                              memory_cap_bytes=cap))
        assert all(s.peak_bytes <= cap for s in p.schedule)

        x = lowrank(shape, ranks)
        jax.block_until_ready(x)
        base = _live_bytes()
        y, high = x, 0
        for step in p.schedule:
            res = solve_step(y, step, als_iters=p.config.als_iters)
            jax.block_until_ready(res.y_new)
            y = res.y_new
            high = max(high, _live_bytes() - base)
        # boundary samples see the shrunken tensor + factors, never the
        # busted-cap working set the uncapped plan would have carried
        assert high <= cap


# ---------------------------------------------------------------------------
# TuckerBatchEngine cap pinning
# ---------------------------------------------------------------------------

class TestEngineCapPin:
    def test_engine_pins_cap_onto_request_configs(self):
        from repro.serve.engine import TuckerBatchEngine, TuckerRequest

        shape, ranks = (16, 96, 64), (12, 4, 8)
        nat = plan(shape, jnp.float32, TuckerConfig(ranks=ranks))
        cap = int(max(s.peak_bytes for s in nat.schedule) * 0.8)
        eng = TuckerBatchEngine(memory_cap_bytes=cap)
        reqs = [TuckerRequest(x=lowrank(shape, ranks, seed=s),
                              config=TuckerConfig(ranks=ranks,
                                                  mode_order="opt"))
                for s in range(3)]
        eng.run(reqs)
        assert all(r.result is not None for r in reqs)
        (plan_built,) = eng._plans.values()
        assert plan_built.config.memory_cap_bytes == cap
        assert all(s.peak_bytes <= cap for s in plan_built.schedule)

    def test_request_keeps_tighter_cap(self):
        from repro.serve.engine import TuckerBatchEngine

        eng = TuckerBatchEngine(memory_cap_bytes=10**9)
        cfg = TuckerConfig(ranks=(2, 2, 2), memory_cap_bytes=10**8)
        assert eng._pinned(cfg).memory_cap_bytes == 10**8
        loose = TuckerConfig(ranks=(2, 2, 2))
        assert eng._pinned(loose).memory_cap_bytes == 10**9

    def test_infeasible_engine_cap_fails_at_plan_time(self):
        from repro.serve.engine import TuckerBatchEngine, TuckerRequest

        eng = TuckerBatchEngine(memory_cap_bytes=1000)
        req = TuckerRequest(x=lowrank((16, 12, 10), (2, 2, 2)),
                            config=TuckerConfig(ranks=(2, 2, 2),
                                                mode_order="opt"))
        with pytest.raises(MemoryCapError):
            eng.run([req])


# ---------------------------------------------------------------------------
# Review regressions
# ---------------------------------------------------------------------------

class TestReviewRegressions:
    def test_als_zero_iters_rejected(self):
        from repro.core.solvers import als_solve

        x = lowrank((10, 8, 6), (3, 3, 3))
        with pytest.raises(ValueError, match="num_iters"):
            als_solve(x, 0, 3, num_iters=0)

    def test_undonated_plan_cap_counts_held_input(self):
        # every step fits the cap, but an UNDONATED sweep also keeps the
        # dead input copy alive through steps 1..N-1 — the plan must refuse
        shape, ranks = (24, 32, 20), (4, 4, 4)
        donated = plan(shape, jnp.float32,
                       TuckerConfig(ranks=ranks, donate_input=True))
        # the premise: a later step plus the held input outgrows step 0
        assert plan(shape, jnp.float32,
                    TuckerConfig(ranks=ranks, donate_input=False)
                    ).peak_bytes > donated.peak_bytes
        cap = donated.peak_bytes + 1   # fits per step and when donated
        assert plan(shape, jnp.float32,
                    TuckerConfig(ranks=ranks, donate_input=True,
                                 memory_cap_bytes=cap)).peak_bytes <= cap
        with pytest.raises(MemoryCapError, match="undonated"):
            plan(shape, jnp.float32,
                 TuckerConfig(ranks=ranks, donate_input=False,
                              memory_cap_bytes=cap))

    def test_per_call_donate_overrides_config_false(self):
        if not donation_supported(jax.default_backend()):
            pytest.skip("platform has no buffer donation")
        p = plan((32, 24, 20), jnp.float32,
                 TuckerConfig(ranks=(4, 4, 4), methods="eig",
                              donate_input=False))
        x = jnp.asarray(np.asarray(lowrank((32, 24, 20), (4, 4, 4))))
        res = p.execute(x, donate=True)
        jax.block_until_ready(res.tucker.core)
        assert x.is_deleted()

    def test_input_bytes_uses_storage_dtype(self):
        # the buffer an undonated sweep holds is x AS PASSED (bf16); the
        # fp32 cast happens inside the jit and is not the held copy
        p = plan((32, 24, 20), jnp.bfloat16,
                 TuckerConfig(ranks=(4, 4, 4), methods="eig",
                              compute_dtype="float32"))
        assert p.input_bytes == 32 * 24 * 20 * 2
