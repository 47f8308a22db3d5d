"""Plan/execute front door: schedule resolution, compiled-sweep caching,
batched execution, JSON round-trip, and exact parity with the legacy path."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (TuckerConfig, TuckerPlan, decompose, plan, sthosvd,
                        tensor_ops as T)
from repro.core import api as api_mod
from repro.core.variants import hooi, thosvd


def lowrank(dims, ranks, seed=0, noise=0.0):
    rng = np.random.default_rng(seed)
    core = rng.standard_normal(ranks)
    us = [np.linalg.qr(rng.standard_normal((d, r)))[0]
          for d, r in zip(dims, ranks)]
    x = T.reconstruct(jnp.asarray(core, jnp.float32),
                      [jnp.asarray(u, jnp.float32) for u in us])
    if noise:
        rms = float(jnp.sqrt(jnp.mean(x ** 2)))
        x = x + noise * rms * jnp.asarray(rng.standard_normal(dims), jnp.float32)
    return x


class TestConfig:
    def test_normalization_and_validation(self):
        c = TuckerConfig(ranks=[3, 4, 2], methods=["eig", "als", "eig"],
                         mode_order=[2, 0, 1])
        assert c.ranks == (3, 4, 2)
        assert c.methods == ("eig", "als", "eig")
        assert c.mode_order == (2, 0, 1)
        with pytest.raises(ValueError):
            TuckerConfig(ranks=(2, 2), variant="cp")
        with pytest.raises(ValueError):
            TuckerConfig(ranks=(2, 2), impl="magic")
        with pytest.raises(ValueError):
            TuckerConfig(ranks=(2, 2), als_iters=0)

    def test_dict_roundtrip(self):
        c = TuckerConfig(ranks=(3, 4, 2), variant="hooi", methods="auto",
                         mode_order="shrink", als_iters=7, hooi_iters=2,
                         compute_dtype="float32")
        assert TuckerConfig.from_dict(c.to_dict()) == c


class TestPlanning:
    def test_schedule_resolved_ahead_of_time(self):
        calls = []

        def sel(*, i_n, r_n, j_n):
            calls.append((i_n, r_n, j_n))
            return "eig"

        p = plan((10, 12, 8), jnp.float32, TuckerConfig(ranks=(3, 4, 2)),
                 selector=sel)
        # selector saw the same shrinking J_n the legacy in-loop path sees
        assert calls == [(10, 3, 96), (12, 4, 24), (8, 2, 12)]
        assert p.methods == ("eig", "eig", "eig")
        assert p.total_flops > 0 and p.peak_bytes > 0
        assert p.select_seconds >= 0.0

    def test_invalid_inputs(self):
        p = plan((10, 12, 8), jnp.float32, TuckerConfig(ranks=(3, 4, 2),
                                                        methods="eig"))
        with pytest.raises(ValueError):
            p.execute(jnp.zeros((10, 12, 9), jnp.float32))
        with pytest.raises(ValueError):
            p.execute(jnp.zeros((10, 12, 8), jnp.bfloat16))
        with pytest.raises(ValueError):
            p.execute_batch(jnp.zeros((2, 10, 12, 9), jnp.float32))
        with pytest.raises(ValueError):
            plan((10, 12), jnp.float32, TuckerConfig(ranks=(3, 4, 2)))
        with pytest.raises(ValueError):   # mode_order is meaningless there
            plan((10, 12, 8), jnp.float32,
                 TuckerConfig(ranks=(3, 4, 2), variant="thosvd",
                              mode_order=(2, 0, 1), methods="eig"))

    def test_hooi_schedule_shape(self):
        cfg = TuckerConfig(ranks=(3, 4, 2), variant="hooi", methods="eig",
                           hooi_iters=2)
        p = plan((10, 12, 8), jnp.float32, cfg)
        assert len(p.schedule) == 3 + 2 * 3       # init sweep + 2 sweeps
        # refinement steps see x projected on all other factors
        s = p.schedule[3]
        assert (s.i_n, s.r_n, s.j_n) == (10, 3, 4 * 2)


class TestExecuteParity:
    @pytest.mark.parametrize("impl", ["matfree", "explicit"])
    def test_execute_matches_legacy_bitwise(self, impl):
        """Acceptance: same resolved schedule → bitwise-identical results,
        from the compiled sweep, the recorded path and the legacy call."""
        x = lowrank((12, 15, 10), (3, 4, 2), noise=0.05)
        p = plan(x.shape, x.dtype, TuckerConfig(ranks=(3, 4, 2), impl=impl))
        legacy = sthosvd(x, (3, 4, 2), methods=p.methods, impl=impl)
        res = p.execute(x)
        rec = p.execute(x, record=True)
        for other in (legacy, rec):
            assert bool(jnp.all(res.tucker.core == other.tucker.core))
            for u_new, u_old in zip(res.tucker.factors, other.tucker.factors):
                assert bool(jnp.all(u_new == u_old))

    @pytest.mark.parametrize("impl", ["matfree", "explicit"])
    @pytest.mark.parametrize("variant,legacy_fn", [
        ("thosvd", lambda x, r, impl: thosvd(x, r, methods="eig", impl=impl)),
        ("hooi", lambda x, r, impl: hooi(x, r, n_iters=2, methods="eig",
                                         impl=impl)),
    ])
    def test_variant_plans_match_legacy(self, variant, legacy_fn, impl):
        x = lowrank((10, 9, 8), (2, 3, 2), noise=0.05)
        cfg = TuckerConfig(ranks=(2, 3, 2), variant=variant, methods="eig",
                           hooi_iters=2, impl=impl)
        p = plan(x.shape, x.dtype, cfg)
        res = p.execute(x)
        rec = p.execute(x, record=True)
        assert all(t.seconds > 0 for t in rec.trace)
        assert bool(jnp.all(res.tucker.core == rec.tucker.core))
        for u_new, u_rec in zip(res.tucker.factors, rec.tucker.factors):
            assert bool(jnp.all(u_new == u_rec))
        ref = legacy_fn(x, (2, 3, 2), impl)
        np.testing.assert_allclose(np.asarray(res.tucker.core),
                                   np.asarray(ref.tucker.core),
                                   rtol=1e-5, atol=1e-5)

    def test_mode_order_and_als_iters_respected(self):
        x = lowrank((20, 6, 8), (2, 3, 2), noise=0.01)
        cfg = TuckerConfig(ranks=(2, 3, 2), methods="als", als_iters=8,
                           mode_order="shrink")
        p = plan(x.shape, x.dtype, cfg)
        assert p.schedule[0].mode == 0            # biggest shrink first
        legacy = sthosvd(x, (2, 3, 2), methods="als", als_iters=8,
                         mode_order="shrink")
        res = p.execute(x)
        assert bool(jnp.all(res.tucker.core == legacy.tucker.core))

    def test_decompose_convenience(self):
        x = lowrank((12, 10, 8), (3, 3, 2))
        res = decompose(x, TuckerConfig(ranks=(3, 3, 2), methods="eig"))
        assert float(res.tucker.rel_error(x)) < 1e-4


class TestBatch:
    def test_execute_batch_matches_per_item_loop(self):
        xs = jnp.stack([lowrank((10, 9, 8), (2, 3, 2), seed=s, noise=0.05)
                        for s in range(4)])
        p = plan(xs.shape[1:], xs.dtype, TuckerConfig(ranks=(2, 3, 2)))
        batch = p.execute_batch(xs)
        assert len(batch) == 4
        for b, res in enumerate(batch):
            one = p.execute(xs[b])
            # batched GEMMs may round differently → allclose, not bitwise
            np.testing.assert_allclose(np.asarray(res.tucker.core),
                                       np.asarray(one.tucker.core),
                                       rtol=1e-4, atol=1e-4)
            xhat_b = res.tucker.reconstruct()
            xhat_1 = one.tucker.reconstruct()
            np.testing.assert_allclose(np.asarray(xhat_b), np.asarray(xhat_1),
                                       rtol=1e-4, atol=1e-4)


class TestCompileCache:
    def test_plan_reuse_zero_recompiles_zero_selections(self):
        """Acceptance: repeated executes on same-shaped inputs hit the cached
        compiled sweep (no retraces) and never touch the selector."""
        api_mod.clear_sweep_cache()
        selections = []

        def sel(*, i_n, r_n, j_n):
            selections.append((i_n, r_n, j_n))
            return "eig"

        x = lowrank((12, 10, 8), (3, 3, 2), noise=0.05)
        p = plan(x.shape, x.dtype, TuckerConfig(ranks=(3, 3, 2)), selector=sel)
        n_plan_selections = len(selections)
        assert n_plan_selections == 3

        p.execute(x)
        after_first = dict(api_mod.CACHE_STATS)
        assert after_first["builds"] == 1 and after_first["traces"] == 1

        for s in range(5):
            p.execute(x + float(s))
        assert api_mod.CACHE_STATS["traces"] == after_first["traces"]
        assert api_mod.CACHE_STATS["builds"] == after_first["builds"]
        assert api_mod.CACHE_STATS["hits"] == after_first["hits"] + 5
        assert len(selections) == n_plan_selections   # zero at execute time

    def test_equivalent_plans_share_compiled_sweep(self):
        api_mod.clear_sweep_cache()
        x = lowrank((10, 9, 8), (2, 3, 2))
        cfg = TuckerConfig(ranks=(2, 3, 2), methods="eig")
        plan(x.shape, x.dtype, cfg).execute(x)
        plan(x.shape, x.dtype, cfg).execute(x)     # fresh plan, same key
        assert api_mod.CACHE_STATS["builds"] == 1
        assert api_mod.CACHE_STATS["hits"] == 1
        assert api_mod.CACHE_STATS["traces"] == 1

    def test_batched_program_cached_separately(self):
        api_mod.clear_sweep_cache()
        xs = jnp.stack([lowrank((10, 9, 8), (2, 3, 2), seed=s)
                        for s in range(2)])
        p = plan(xs.shape[1:], xs.dtype,
                 TuckerConfig(ranks=(2, 3, 2), methods="eig"))
        p.execute_batch(xs)
        p.execute_batch(xs)
        assert api_mod.CACHE_STATS["builds"] == 1
        assert api_mod.CACHE_STATS["hits"] == 1
        assert api_mod.CACHE_STATS["traces"] == 1


class TestSerialization:
    def test_json_roundtrip_preserves_schedule_and_results(self, tmp_path):
        x = lowrank((12, 10, 8), (3, 3, 2), noise=0.05)
        p = plan(x.shape, x.dtype,
                 TuckerConfig(ranks=(3, 3, 2), variant="sthosvd"))
        path = tmp_path / "plan.json"
        p.save(path)
        p2 = TuckerPlan.load(path)
        assert p2.shape == p.shape and p2.dtype == p.dtype
        assert p2.config == p.config
        assert p2.schedule == p.schedule
        r1, r2 = p.execute(x), p2.execute(x)
        assert bool(jnp.all(r1.tucker.core == r2.tucker.core))

    def test_loaded_plan_never_selects(self, tmp_path):
        p = plan((10, 9, 8), jnp.float32, TuckerConfig(ranks=(2, 3, 2)),
                 selector=lambda *, i_n, r_n, j_n: "als")
        path = tmp_path / "p.json"
        p.save(path)
        p2 = TuckerPlan.load(path)
        assert p2.methods == ("als", "als", "als")  # frozen choice survives

    def test_version_guard(self):
        d = plan((4, 4, 4), jnp.float32,
                 TuckerConfig(ranks=(2, 2, 2), methods="eig")).to_dict()
        d["version"] = 999
        with pytest.raises(ValueError):
            TuckerPlan.from_dict(d)


class TestServeEngine:
    def test_groups_by_shape_and_reuses_plans(self):
        from repro.serve import TuckerBatchEngine, TuckerRequest

        eng = TuckerBatchEngine()
        cfg = TuckerConfig(ranks=(2, 3, 2), methods="eig")
        reqs = [TuckerRequest(x=lowrank((10, 9, 8), (2, 3, 2), seed=s),
                              config=cfg, rid=s) for s in range(5)]
        reqs += [TuckerRequest(x=lowrank((6, 7, 5), (2, 2, 2), seed=9),
                               config=TuckerConfig(ranks=(2, 2, 2),
                                                   methods="eig"), rid=99)]
        done = eng.run(reqs)
        assert all(r.result is not None for r in done)
        assert eng.stats["plans_built"] == 2       # one per (shape, config)
        for r in done:
            assert float(r.result.tucker.rel_error(r.x)) < 1e-3
        # second wave with the same shapes: no new plans
        wave2 = [TuckerRequest(x=lowrank((10, 9, 8), (2, 3, 2), seed=7),
                               config=cfg, rid=7)]
        eng.run(wave2)
        assert eng.stats["plans_built"] == 2
