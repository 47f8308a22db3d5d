"""Rank-adaptive planning: the `rand` solver family, error-targeted plans
(`TuckerConfig(error_target=...)`), the rank axis in the schedule DP, the
selector's widened candidate set, achieved-error labels in the tune store,
and adaptive configs flowing through serving."""

from dataclasses import replace

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import (TuckerConfig, TuckerPlan, plan, rand_sketch,
                        rand_solve, tensor_ops as T)
from repro.core.backend import backend_ops
from repro.core.cost_model import CostModel
from repro.core.schedule_opt import optimize_schedule
from repro.core.selector import Selector
from repro.core.sthosvd import ModeTrace
from repro.tune.collect import measurements_from_traces
from repro.tune.records import Measurement
from repro.tune.train import labeled_examples


def lowrank(dims, ranks, seed=0, noise=0.01):
    rng = np.random.default_rng(seed)
    core = rng.standard_normal(ranks)
    us = [np.linalg.qr(rng.standard_normal((d, r)))[0]
          for d, r in zip(dims, ranks)]
    x = T.reconstruct(jnp.asarray(core, jnp.float32),
                      [jnp.asarray(u, jnp.float32) for u in us])
    if noise:
        rms = float(jnp.sqrt(jnp.mean(x ** 2)))
        x = x + noise * rms * jnp.asarray(rng.standard_normal(dims),
                                          jnp.float32)
    return x


DIMS, TRUE_RANKS, EPS = (60, 40, 24), (6, 5, 4), 0.05


class TestRandSolver:
    def test_rand_solve_recovers_lowrank_subspace(self):
        x = lowrank(DIMS, TRUE_RANKS, noise=0.0)
        y, factors = x, {}
        for mode, r in enumerate(TRUE_RANKS):
            res = rand_solve(y, mode, r)
            factors[mode] = res.u
            y = res.y_new
        # orthonormal factors, near-exact reconstruction at the true ranks
        for u in factors.values():
            eye = np.eye(u.shape[1], dtype=np.float32)
            np.testing.assert_allclose(np.asarray(u.T @ u), eye, atol=1e-4)
        xh = T.reconstruct(y, [factors[m] for m in range(len(DIMS))])
        err = float(jnp.linalg.norm(x - xh) / jnp.linalg.norm(x))
        assert err < 1e-3

    def test_sketch_tail_is_exact_for_the_used_factor(self):
        # the rank decision's tail — energy minus the top-r sketched
        # eigenvalues — must equal the true discarded energy of the factor
        # u = q·v actually built from the sketch, at ANY width
        x = lowrank((30, 20, 16), (5, 4, 3), noise=0.05)
        width = 12
        q, b, evals, vecs, energy = rand_sketch(x, 0, width)
        ev = np.asarray(evals, dtype=np.float64)
        ttm = backend_ops("matfree")[0]
        for r in (2, 4, 8):
            v = vecs[:, -r:][:, ::-1].astype(q.dtype)
            u = jnp.dot(q, v)
            resid = x - ttm(ttm(x, u.T, 0), u, 0)
            actual = float(jnp.linalg.norm(resid)) ** 2
            modeled = float(energy) - float(ev[::-1][:r].sum())
            assert actual == pytest.approx(modeled, rel=1e-3, abs=1e-2)

    def test_rand_is_exposed_as_a_solver(self):
        from repro.core import RAND
        from repro.core.solvers import SOLVERS
        assert RAND == "rand" and "rand" in SOLVERS


class TestAdaptiveConfig:
    def test_ranks_none_requires_error_target(self):
        with pytest.raises(ValueError):
            TuckerConfig()

    @pytest.mark.parametrize("eps", [0.0, 1.0, -0.1, 2.0])
    def test_error_target_range(self, eps):
        with pytest.raises(ValueError):
            TuckerConfig(error_target=eps)

    def test_error_target_rejects_incompatible_modes(self):
        with pytest.raises(ValueError):
            TuckerConfig(error_target=0.05, variant="hooi")
        with pytest.raises(ValueError):
            TuckerConfig(error_target=0.05, mode_parallel="auto")
        with pytest.raises(ValueError):
            TuckerConfig(error_target=0.05, impl="sharded")

    def test_rank_grid_requires_error_target(self):
        with pytest.raises(ValueError):
            TuckerConfig(ranks=(4, 4, 4), rank_grid=(2, 4))

    def test_rank_grid_normalization_and_roundtrip(self):
        c = TuckerConfig(error_target=0.05, rank_grid=[2, 4, 8],
                         oversample=4, power_iters=2)
        assert c.rank_grid == (2, 4, 8)
        assert TuckerConfig.from_dict(c.to_dict()) == c
        per_mode = TuckerConfig(error_target=0.05,
                                rank_grid=((2, 4), (3, 6), (2,)))
        assert TuckerConfig.from_dict(per_mode.to_dict()) == per_mode


class TestAdaptiveExecution:
    def test_error_target_met_by_refined_sweep(self):
        x = lowrank(DIMS, TRUE_RANKS)
        p = plan(DIMS, jnp.float32, TuckerConfig(error_target=EPS))
        assert p.is_adaptive
        res = p.execute(x)
        err = float(res.tucker.rel_error(x))
        assert err <= EPS
        assert res.error_bound <= EPS
        assert err <= res.error_bound * 1.05  # bound is honest, not slack
        # refined sweep ran the classic solvers; sketch cost is selection
        assert all(t.method in ("eig", "als") for t in res.trace)
        assert res.select_overhead_s > 0.0
        assert any(t.tail_err > 0.0 for t in res.trace)
        # the policy found (at most a few above) the true ranks, not I_n
        assert all(r <= 2 * t for r, t in zip(res.tucker.ranks, TRUE_RANKS))

    def test_sketch_only_execution(self):
        x = lowrank(DIMS, TRUE_RANKS)
        p = plan(DIMS, jnp.float32,
                 TuckerConfig(error_target=EPS, methods="rand"))
        res = p.execute(x)
        assert all(t.method == "rand" for t in res.trace)
        assert float(res.tucker.rel_error(x)) <= EPS
        assert res.error_bound <= EPS

    def test_rank_grid_restricts_choices(self):
        x = lowrank(DIMS, TRUE_RANKS)
        p = plan(DIMS, jnp.float32,
                 TuckerConfig(error_target=EPS, rank_grid=(4, 8)))
        res = p.execute(x)
        assert all(r in (4, 8) for r in res.tucker.ranks)
        assert float(res.tucker.rel_error(x)) <= EPS

    def test_ranks_cap_the_default_grid(self):
        x = lowrank(DIMS, TRUE_RANKS)
        p = plan(DIMS, jnp.float32,
                 TuckerConfig(ranks=(5, 4, 3), error_target=EPS))
        res = p.execute(x)
        assert all(r <= c for r, c in zip(res.tucker.ranks, (5, 4, 3)))

    def test_resolve_ranks(self):
        x = lowrank(DIMS, TRUE_RANKS)
        p = plan(DIMS, jnp.float32, TuckerConfig(error_target=EPS))
        ranks, bound = p.resolve_ranks(x)
        assert len(ranks) == 3 and all(1 <= r <= d
                                       for r, d in zip(ranks, DIMS))
        assert 0.0 <= bound <= EPS
        fixed = plan(DIMS, jnp.float32, TuckerConfig(ranks=(4, 4, 4)))
        with pytest.raises(ValueError):
            fixed.resolve_ranks(x)

    def test_execute_batch_item_by_item(self):
        xs = jnp.stack([lowrank(DIMS, TRUE_RANKS, seed=s) for s in range(2)])
        p = plan(DIMS, jnp.float32, TuckerConfig(error_target=EPS))
        out = p.execute_batch(xs)
        assert len(out) == 2
        for r, xi in zip(out, xs):
            assert float(r.tucker.rel_error(xi)) <= EPS


def old_ladder(p, x):
    """The sketch pass as it ran op by op, reading the eigenvalues and the
    energy separately and waiting for each shrunk tensor: the reference
    the one-read-per-width pass must reproduce.  Returns ``(ranks, tails,
    widths, missed, factors, core)`` with ``widths`` per step as
    ``(final width, widths tried)``."""
    cfg = p.config
    y, total = x, None
    ranks, tails, factors, widths, missed = {}, {}, {}, [], []
    for s in p.schedule:
        cap = min(s.i_n, s.rank_grid[-1] + cfg.oversample)
        width = min(cap, max(16, 2 * cfg.oversample,
                             s.rank_grid[0] + cfg.oversample))
        tried = 0
        while True:
            q, b, evals, vecs, energy = rand_sketch(
                y, s.mode, width, power_iters=cfg.power_iters,
                impl=s.backend)
            tried += 1
            ev = np.maximum(np.asarray(evals, dtype=np.float64), 0.0)
            energy = float(energy)
            if total is None:
                total = energy or 1.0
            csum = np.cumsum(ev[::-1])
            r = tail = None
            for cand in s.rank_grid:
                if cand > width:
                    break
                t = max(energy - float(csum[cand - 1]), 0.0)
                if t <= s.tau * total:
                    r, tail = cand, t
                    break
            if r is not None or width >= cap:
                break
            width = min(2 * width, cap)
        if r is None:
            r = max(g for g in s.rank_grid if g <= width)
            tail = max(energy - float(csum[r - 1]), 0.0)
            missed.append(s.mode)
        ranks[s.mode], tails[s.mode] = r, tail / total
        widths.append((width, tried))
        v = vecs[:, -r:][:, ::-1].astype(q.dtype)
        factors[s.mode] = jnp.dot(q, v, precision=jax.lax.Precision.HIGHEST)
        y = backend_ops(s.backend)[0](b, v.T, s.mode).astype(x.dtype)
        jax.block_until_ready(y)
    n = len(p.shape)
    return (tuple(ranks[m] for m in range(n)), tails, widths, missed,
            [factors[m] for m in range(n)], y)


#: (dims, true ranks, seed, noise, error target, rank grid): the ladder
#: staying narrow on three seeds, widening to the cap (I_n) on every mode,
#: widening once, and a rank grid met and missed
LADDERS = [
    (DIMS, TRUE_RANKS, 0, 0.01, EPS, None),
    (DIMS, TRUE_RANKS, 1, 0.01, EPS, None),
    (DIMS, TRUE_RANKS, 2, 0.01, EPS, None),
    ((60, 40, 24), (30, 25, 20), 1, 0.2, 0.02, None),
    ((60, 40, 24), (30, 25, 20), 1, 0.05, EPS, None),
    ((48, 40, 36), (20, 22, 18), 1, 0.02, 0.03, None),
    (DIMS, TRUE_RANKS, 1, 0.01, EPS, (4, 8)),
    (DIMS, TRUE_RANKS, 1, 0.3, 0.02, (4, 8)),
]


def _sketch_spans(events):
    return [e for e in obs.iter_spans(events) if e["name"] == "sketch"]


class TestSketchPassEquivalence:
    """The pass that reads each sketch width once and shrinks in one
    compiled program decides exactly as the op-by-op ladder did.  Tails
    are fractions of ||X||²: each is a difference of float32 eigenvalue
    sums, good to about 1e-7·||X||², and the compiled shrink may change
    the last bits of the tensor the next mode sketches, so tails (and the
    bound's square, their sum) agree to 1e-6 of ||X||²."""

    @pytest.mark.parametrize("dims, true_ranks, seed, noise, eps, grid",
                             LADDERS)
    def test_same_ranks_widths_and_tails(self, dims, true_ranks, seed,
                                         noise, eps, grid):
        x = lowrank(dims, true_ranks, seed=seed, noise=noise)
        p = plan(dims, jnp.float32,
                 TuckerConfig(error_target=eps, rank_grid=grid))
        ranks, tails, widths, missed, _, _ = old_ladder(p, x)
        with obs.capture() as buf:
            got_ranks, got_tails, *_, got_missed = p._sketch_pass(x)
        got_widths = [(e["width"], e["widths"])
                      for e in _sketch_spans(buf.events())]
        assert got_ranks == ranks
        assert got_missed == missed
        assert got_widths == widths
        for m in tails:
            assert got_tails[m] == pytest.approx(tails[m], rel=1e-6,
                                                 abs=1e-6)
        _, bound = p.resolve_ranks(x)
        assert bound ** 2 == pytest.approx(sum(tails.values()), rel=1e-6,
                                           abs=1e-6)

    def test_the_ladder_cases_cover_widening_and_misses(self):
        widened = capped = missed = False
        for dims, true_ranks, seed, noise, eps, grid in LADDERS:
            x = lowrank(dims, true_ranks, seed=seed, noise=noise)
            p = plan(dims, jnp.float32,
                     TuckerConfig(error_target=eps, rank_grid=grid))
            _, _, widths, miss, _, _ = old_ladder(p, x)
            widened |= any(tried > 1 for _, tried in widths)
            capped |= any(w == s.i_n and tried > 1
                          for (w, tried), s in zip(widths, p.schedule))
            missed |= bool(miss)
        assert widened and capped and missed

    @pytest.mark.parametrize("dims, true_ranks, seed, noise, eps, grid",
                             LADDERS)
    def test_sketch_only_factors_and_core(self, dims, true_ranks, seed,
                                          noise, eps, grid):
        x = lowrank(dims, true_ranks, seed=seed, noise=noise)
        p = plan(dims, jnp.float32,
                 TuckerConfig(error_target=eps, rank_grid=grid,
                              methods="rand"))
        ranks, _, _, missed, factors, core = old_ladder(p, x)
        res = p.execute(x)
        assert res.tucker.ranks == ranks
        if missed:      # the rand→eig hop refined: no sketch factors
            assert all(t.method == "eig" for t in res.trace)
            return
        # an eigenvector's sign is free: align each column before comparing
        got_core = np.asarray(res.tucker.core)
        for m, (u, ref) in enumerate(zip(res.tucker.factors, factors)):
            u, ref = np.asarray(u), np.asarray(ref)
            sign = np.sign(np.sum(u * ref, axis=0))
            np.testing.assert_allclose(u * sign, ref, atol=1e-4)
            shape = [1] * got_core.ndim
            shape[m] = -1
            got_core = got_core * sign.reshape(shape)
        scale = float(np.max(np.abs(np.asarray(core))))
        np.testing.assert_allclose(got_core, np.asarray(core),
                                   atol=1e-4 * scale)


class TestSketchPassSyncs:
    def test_one_blocking_read_per_width(self):
        x = lowrank((60, 40, 24), (30, 25, 20), seed=1, noise=0.05)
        p = plan((60, 40, 24), jnp.float32, TuckerConfig(error_target=EPS))
        with obs.capture() as buf:
            p._sketch_pass(x)
        spans = list(obs.iter_spans(buf.events()))
        sketches = [e for e in spans if e["name"] == "sketch"]
        readbacks = [e for e in spans if e["name"] == "sketch.readback"]
        assert len(sketches) == 3
        assert sum(e["widths"] for e in sketches) > 3   # the ladder widened
        for e in sketches:
            assert e["syncs"] == e["widths"]
        assert len(readbacks) == sum(e["widths"] for e in sketches)

    def test_same_shape_same_ranks_compiles_no_new_shrink(self):
        from repro.core.solvers import ritz_shrink, sketch_readout
        p = plan(DIMS, jnp.float32, TuckerConfig(error_target=EPS))
        first = p.execute(lowrank(DIMS, TRUE_RANKS, seed=0))
        shrinks, readouts = (ritz_shrink._cache_size(),
                             sketch_readout._cache_size())
        second = p.execute(lowrank(DIMS, TRUE_RANKS, seed=1))
        assert second.tucker.ranks == first.tucker.ranks
        assert ritz_shrink._cache_size() == shrinks
        assert sketch_readout._cache_size() == readouts


class TestAdaptivePlanJSON:
    def test_adaptive_plan_round_trips(self):
        p = plan(DIMS, jnp.float32,
                 TuckerConfig(error_target=EPS, rank_grid=(4, 8),
                              oversample=4, power_iters=2))
        p2 = TuckerPlan.from_json(p.to_json())
        assert p2.is_adaptive
        assert p2.config == p.config
        assert p2.describe() == p.describe()
        assert [ (s.mode, s.rank_grid, s.tau) for s in p2.schedule ] == \
               [ (s.mode, s.rank_grid, s.tau) for s in p.schedule ]
        x = lowrank(DIMS, TRUE_RANKS)
        assert float(p2.execute(x).tucker.rel_error(x)) <= EPS

    def test_describe_names_the_policy(self):
        p = plan(DIMS, jnp.float32, TuckerConfig(error_target=EPS))
        d = p.describe()
        assert "error_target=0.05" in d and "rank-adaptive" in d
        assert "grid=" in d


class TestScheduleDPRankAxis:
    def test_legacy_fixed_ranks_unchanged(self):
        rs = optimize_schedule((30, 20, 10), (8, 6, 4))
        fixed = (8, 6, 4)
        assert rs.ranks == tuple(fixed[m] for m in rs.order)

    def test_grid_opens_the_rank_axis(self):
        rs = optimize_schedule((30, 20, 10), (8, 6, 4),
                               methods=["rand"] * 3,
                               rank_grid=[(2, 8), (2, 6), (2, 4)])
        grids = {0: (2, 8), 1: (2, 6), 2: (2, 4)}
        assert all(r in grids[m] for m, r in zip(rs.order, rs.ranks))
        # with no accuracy term in the DP objective the cheapest (smallest)
        # grid rank wins every mode
        assert rs.ranks == (2, 2, 2)


class TestSelectorCandidates:
    def test_candidates_widen_the_cost_fallback(self):
        cheap = Selector(cost_model=CostModel(rand_scale=1e-12))
        kw = dict(i_n=500, r_n=8, j_n=400)
        assert cheap(**kw, candidates=("eig", "als", "rand")) == "rand"
        assert cheap(**kw) in ("eig", "als")
        dear = Selector(cost_model=CostModel(rand_scale=1e12))
        assert dear(**kw, candidates=("eig", "als", "rand")) in ("eig", "als")

    def test_rand_scale_falls_back_to_eig(self):
        assert CostModel().rand_scale_eff == CostModel().eig_scale
        assert CostModel(eig_scale=5e-12).rand_scale_eff == 5e-12
        assert CostModel(rand_scale=3e-12).rand_scale_eff == 3e-12
        assert CostModel.from_dict({}).rand_scale is None


class TestTuneAchievedErrorLabels:
    MEAS = dict(platform="cpu", backend="matfree", device="box",
                i_n=32, r_n=4, j_n=64, method="rand", seconds=0.01)

    def test_rel_err_round_trips_and_is_not_identity(self):
        m = Measurement(**self.MEAS, rel_err=0.02)
        assert Measurement.from_dict(m.to_dict()) == m
        assert m.key() == replace(m, rel_err=0.5).key()

    def test_rand_traces_harvest_with_tail_labels(self):
        traces = [
            ModeTrace(mode=0, method="rand", i_n=32, r_n=4, j_n=64,
                      seconds=0.01, tail_err=0.003),
            ModeTrace(mode=1, method="eig", i_n=16, r_n=4, j_n=128,
                      seconds=0.02),
            ModeTrace(mode=2, method="svd", i_n=8, r_n=2, j_n=64,
                      seconds=0.02),
        ]
        ms = measurements_from_traces(traces, platform="cpu",
                                      dtype="float32", order=3)
        assert [m.method for m in ms] == ["rand", "eig"]  # svd filtered
        assert ms[0].rel_err == pytest.approx(0.003)
        assert ms[1].rel_err == 0.0

    def test_labeled_examples_tolerance_drops_lossy_records(self):
        eig = Measurement(**{**self.MEAS, "method": "eig",
                             "seconds": 1.0})
        als = Measurement(**{**self.MEAS, "method": "als",
                             "seconds": 0.1}, rel_err=0.5)
        _, labels, _ = labeled_examples([eig, als])
        assert len(labels) == 1          # lossy-but-fast als wins unfiltered
        _, labels, _ = labeled_examples([eig, als], rel_err_tolerance=0.1)
        assert len(labels) == 0          # filtered: no pair survives


class TestServeAdaptive:
    def test_service_serves_error_targeted_requests(self):
        from repro.serve import TuckerService
        x = lowrank(DIMS, TRUE_RANKS)
        cfg = TuckerConfig(error_target=EPS)
        with TuckerService() as svc:
            svc.start()
            res = svc.wait(svc.submit(x, cfg))
            stats = svc.stats()
        assert float(res.tucker.rel_error(x)) <= EPS
        labels = list(stats["buckets"])
        assert any(label.endswith(f"/re{EPS:g}") for label in labels), labels
