"""Ops-backend layer: registry semantics, three-way op parity
(pallas ≡ matfree ≡ explicit, interpret mode on CPU), plan-level routing,
and the dtype-aware peak_bytes model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    OpsBackend,
    TuckerConfig,
    TuckerPlan,
    backend_names,
    get_backend,
    plan,
    register_backend,
    resolve_backend,
    sthosvd,
    tensor_ops as T,
)
from repro.core import api as api_mod
from repro.core.backend import unregister_backend
from repro.core.plan import ModeStep, resolve_schedule
from repro.core.solvers import svd_solve

BACKENDS = ("matfree", "explicit", "pallas")

TOL = {"float32": dict(rtol=3e-4, atol=3e-4),
       "bfloat16": dict(rtol=4e-2, atol=4e-2)}


def arr(shape, dtype=jnp.float32, seed=0):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.standard_normal(shape), dtype)


def lowrank(dims, ranks, seed=0, noise=0.0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    core = rng.standard_normal(ranks)
    us = [np.linalg.qr(rng.standard_normal((d, r)))[0]
          for d, r in zip(dims, ranks)]
    x = T.reconstruct(jnp.asarray(core, jnp.float32),
                      [jnp.asarray(u, jnp.float32) for u in us])
    if noise:
        rms = float(jnp.sqrt(jnp.mean(x ** 2)))
        x = x + noise * rms * jnp.asarray(rng.standard_normal(dims), jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_builtins_registered(self):
        assert set(BACKENDS) <= set(backend_names())

    def test_get_backend_unknown_raises(self):
        with pytest.raises(ValueError, match="unknown backend"):
            get_backend("cublas")

    def test_capability_metadata(self):
        assert get_backend("explicit").matricizes
        assert not get_backend("matfree").matricizes
        p = get_backend("pallas")
        assert p.interpret_fallback and p.native_on("tpu")
        assert not p.supports_dtype(jnp.float64)
        assert p.supports_dtype(jnp.bfloat16)

    def test_auto_resolution_per_platform(self):
        # explicit platform arg: deterministic regardless of test host
        assert resolve_backend("auto", platform="tpu").name == "pallas"
        assert resolve_backend("auto", platform="cpu").name == "matfree"
        assert resolve_backend("auto", platform="gpu").name == "matfree"
        # auto never picks a dtype the backend can't run
        assert resolve_backend("auto", platform="tpu",
                               dtype=jnp.float64).name == "matfree"

    def test_explicit_name_dtype_guard(self):
        with pytest.raises(ValueError, match="does not support dtype"):
            resolve_backend("pallas", dtype=jnp.float64)

    def test_register_custom_backend(self):
        calls = []

        def loud_ttm(x, u, mode):
            calls.append(mode)
            return T.ttm(x, u, mode)

        register_backend(OpsBackend(
            name="loud", loader=lambda: (loud_ttm, T.gram, T.ttt)))
        try:
            with pytest.raises(ValueError, match="already registered"):
                register_backend(OpsBackend(
                    name="loud", loader=lambda: (T.ttm, T.gram, T.ttt)))
            x = lowrank((8, 7, 6), (2, 2, 2))
            cfg = TuckerConfig(ranks=(2, 2, 2), methods="eig", impl="loud")
            p = plan(x.shape, x.dtype, cfg)
            assert p.backend == "loud"
            api_mod.clear_sweep_cache()
            p.execute(x)
            assert calls   # custom ops actually ran inside the sweep
        finally:
            unregister_backend("loud")
            api_mod.clear_sweep_cache()

    def test_auto_name_reserved(self):
        with pytest.raises(ValueError, match="reserved"):
            register_backend(OpsBackend(
                name="auto", loader=lambda: (T.ttm, T.gram, T.ttt)))


# ---------------------------------------------------------------------------
# Three-way op parity (the padding shims get odd shapes; pallas runs in
# interpret mode on CPU)
# ---------------------------------------------------------------------------

# first / interior / last modes, non-128-multiple dims
PARITY_CASES = [((33, 12, 17), 0, 9), ((5, 37, 19), 1, 7),
                ((13, 21, 40), 2, 5), ((4, 9, 11, 6), 2, 3)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
class TestOpParity:
    @pytest.mark.parametrize("shape,mode,r", PARITY_CASES)
    def test_ttm(self, shape, mode, r, dtype):
        x = arr(shape, jnp.dtype(dtype), seed=1)
        u = arr((r, shape[mode]), jnp.dtype(dtype), seed=2)
        outs = {b: get_backend(b).ops()[0](x, u, mode) for b in BACKENDS}
        for b in BACKENDS:
            assert outs[b].shape == outs["matfree"].shape
            assert outs[b].dtype == outs["matfree"].dtype, b
            np.testing.assert_allclose(
                np.asarray(outs[b], np.float32),
                np.asarray(outs["matfree"], np.float32), **TOL[dtype])

    @pytest.mark.parametrize("shape,mode", [(s, m) for s, m, _ in PARITY_CASES])
    def test_gram(self, shape, mode, dtype):
        x = arr(shape, jnp.dtype(dtype), seed=3)
        outs = {b: get_backend(b).ops()[1](x, mode) for b in BACKENDS}
        for b in BACKENDS:
            np.testing.assert_allclose(
                np.asarray(outs[b], np.float32),
                np.asarray(outs["matfree"], np.float32), **TOL[dtype])

    @pytest.mark.parametrize("shape,mode,r", PARITY_CASES)
    def test_ttt(self, shape, mode, r, dtype):
        x = arr(shape, jnp.dtype(dtype), seed=4)
        y = arr(shape[:mode] + (r,) + shape[mode + 1:], jnp.dtype(dtype), seed=5)
        outs = {b: get_backend(b).ops()[2](x, y, mode) for b in BACKENDS}
        for b in BACKENDS:
            np.testing.assert_allclose(
                np.asarray(outs[b], np.float32),
                np.asarray(outs["matfree"], np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# Plan-level routing (the acceptance criterion)
# ---------------------------------------------------------------------------

class TestPlanBackend:
    def test_pallas_plan_matches_matfree(self):
        """plan(impl='pallas').execute ≈ matfree within fp32 accumulation
        tolerance, with the backend recorded in the plan."""
        x = lowrank((12, 15, 10), (3, 4, 2), noise=0.05)
        res = {}
        for b in BACKENDS:
            p = plan(x.shape, x.dtype,
                     TuckerConfig(ranks=(3, 4, 2), methods="eig", impl=b))
            assert p.backend == b
            assert all(s.backend == b for s in p.schedule)
            res[b] = p.execute(x)
            assert res[b].trace[0].backend == b
        for b in BACKENDS[1:]:
            np.testing.assert_allclose(np.asarray(res[b].tucker.core),
                                       np.asarray(res["matfree"].tucker.core),
                                       rtol=1e-4, atol=1e-4)
        assert float(res["pallas"].tucker.rel_error(x)) < 0.06

    def test_pallas_sweep_via_legacy_entry(self):
        x = lowrank((10, 9, 8), (2, 3, 2), noise=0.05)
        r_mf = sthosvd(x, (2, 3, 2), methods="eig", impl="matfree")
        r_pl = sthosvd(x, (2, 3, 2), methods="eig", impl="pallas")
        assert r_pl.trace[0].backend == "pallas"
        np.testing.assert_allclose(np.asarray(r_pl.tucker.core),
                                   np.asarray(r_mf.tucker.core),
                                   rtol=1e-4, atol=1e-4)

    def test_auto_impl_resolves_at_plan_time(self):
        p = plan((8, 7, 6), jnp.float32,
                 TuckerConfig(ranks=(2, 2, 2), methods="eig", impl="auto"))
        want = "pallas" if jax.default_backend() == "tpu" else "matfree"
        assert p.backend == want
        assert p.config.impl == "auto"        # config keeps the request
        d = p.to_dict()                        # ... but JSON carries both
        assert d["schedule"][0]["backend"] == want

    def test_unknown_impl_rejected_at_config(self):
        with pytest.raises(ValueError, match="unknown backend"):
            TuckerConfig(ranks=(2, 2), impl="magic")

    def test_plan_json_roundtrip_preserves_backend(self, tmp_path):
        p = plan((10, 9, 8), jnp.float32,
                 TuckerConfig(ranks=(2, 3, 2), methods="eig", impl="pallas"))
        path = tmp_path / "p.json"
        p.save(path)
        p2 = TuckerPlan.load(path)
        assert p2.backend == "pallas"
        assert p2.schedule == p.schedule

    def test_legacy_plan_json_defaults_to_matfree(self):
        d = plan((6, 5, 4), jnp.float32,
                 TuckerConfig(ranks=(2, 2, 2), methods="eig")).to_dict()
        for s in d["schedule"]:
            del s["backend"]                   # pre-backend plan files
        assert TuckerPlan.from_dict(d).backend == "matfree"

    def test_plan_reuse_zero_recompiles_per_backend(self):
        """Backend is part of the sweep-cache key: reuse hits, switch builds."""
        api_mod.clear_sweep_cache()
        x = lowrank((10, 9, 8), (2, 3, 2))
        for b in ("matfree", "pallas"):
            cfg = TuckerConfig(ranks=(2, 3, 2), methods="eig", impl=b)
            p = plan(x.shape, x.dtype, cfg)
            p.execute(x)
            p.execute(x)
        assert api_mod.CACHE_STATS["builds"] == 2     # one per backend
        assert api_mod.CACHE_STATS["traces"] == 2
        assert api_mod.CACHE_STATS["hits"] == 2       # second execute each

    def test_auto_and_explicit_name_share_compiled_sweep(self):
        api_mod.clear_sweep_cache()
        x = lowrank((8, 7, 6), (2, 2, 2))
        resolved = resolve_backend("auto").name
        plan(x.shape, x.dtype, TuckerConfig(ranks=(2, 2, 2), methods="eig",
                                            impl="auto")).execute(x)
        plan(x.shape, x.dtype, TuckerConfig(ranks=(2, 2, 2), methods="eig",
                                            impl=resolved)).execute(x)
        assert api_mod.CACHE_STATS["builds"] == 1
        assert api_mod.CACHE_STATS["hits"] == 1

    def test_execute_batch_trace_records_backend(self):
        x = lowrank((8, 7, 6), (2, 2, 2))
        p = plan(x.shape, x.dtype,
                 TuckerConfig(ranks=(2, 2, 2), methods="eig", impl="pallas"))
        res = p.execute_batch(jnp.stack([x, x]))
        assert all(t.backend == "pallas" for r in res for t in r.trace)

    def test_engine_backend_axis(self):
        from repro.serve import TuckerBatchEngine, TuckerRequest

        eng = TuckerBatchEngine(impl="pallas")
        cfg = TuckerConfig(ranks=(2, 2, 2), methods="eig")
        reqs = [TuckerRequest(x=lowrank((8, 7, 6), (2, 2, 2), seed=s),
                              config=cfg, rid=s) for s in range(3)]
        eng.run(reqs)
        assert eng.stats["backends"] == {"pallas": 3}
        assert all(r.result is not None for r in reqs)

    def test_engine_pin_merges_mixed_impl_groups(self):
        """Requests differing only in the overridden impl field batch as one
        vmapped wave under an engine-level pin."""
        from repro.serve import TuckerBatchEngine, TuckerRequest

        eng = TuckerBatchEngine(impl="matfree")
        reqs = [TuckerRequest(x=lowrank((8, 7, 6), (2, 2, 2), seed=s),
                              config=TuckerConfig(ranks=(2, 2, 2),
                                                  methods="eig", impl=impl),
                              rid=s)
                for s, impl in enumerate(("auto", "explicit", "matfree"))]
        eng.run(reqs)
        assert eng.stats["batches"] == 1
        assert eng.stats["plans_built"] == 1
        assert all(r.result is not None for r in reqs)


# ---------------------------------------------------------------------------
# Solver-level impl validation (svd_solve satellite)
# ---------------------------------------------------------------------------

class TestSolverImplValidation:
    def test_svd_solve_rejects_unknown_impl(self):
        x = arr((6, 5, 4))
        with pytest.raises(ValueError, match="unknown backend"):
            svd_solve(x, 0, 2, impl="magic")

    def test_svd_solve_accepts_all_backends(self):
        x = arr((6, 5, 4), seed=8)
        base = svd_solve(x, 0, 2, impl="matfree")
        for b in BACKENDS[1:]:
            res = svd_solve(x, 0, 2, impl=b)   # inherently matricizes anyway
            np.testing.assert_allclose(np.asarray(res.y_new),
                                       np.asarray(base.y_new),
                                       rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Dtype-aware peak_bytes (itemsize satellite)
# ---------------------------------------------------------------------------

class TestPeakBytesDtype:
    def test_float64_doubles_float32(self):
        cfg32 = TuckerConfig(ranks=(3, 3, 3), methods="eig")
        p32 = plan((16, 16, 16), jnp.float32, cfg32)
        p64 = plan((16, 16, 16), jnp.float64, cfg32)
        assert p64.peak_bytes == 2 * p32.peak_bytes

    def test_bfloat16_accounts_for_fp32_accumulation(self):
        cfg = TuckerConfig(ranks=(3, 3, 3), methods="eig")
        p32 = plan((16, 16, 16), jnp.float32, cfg)
        p16 = plan((16, 16, 16), jnp.bfloat16, cfg)
        # bf16 I/O halves, but EIG's Gram scratch stays fp32: strictly more
        # than half the fp32 plan, strictly less than the fp32 plan
        assert p32.peak_bytes / 2 < p16.peak_bytes < p32.peak_bytes

    def test_compute_dtype_governs_itemsize(self):
        cfg = TuckerConfig(ranks=(3, 3, 3), methods="eig",
                           compute_dtype="float64")
        p = plan((16, 16, 16), jnp.float32, cfg)
        ref = plan((16, 16, 16), jnp.float64,
                   TuckerConfig(ranks=(3, 3, 3), methods="eig"))
        assert p.peak_bytes == ref.peak_bytes

    def test_resolve_schedule_stamps_backend_and_itemsize(self):
        steps = resolve_schedule((8, 8, 8), (2, 2, 2), methods="eig",
                                 itemsize=8, backend="explicit")
        assert all(isinstance(s, ModeStep) and s.backend == "explicit"
                   for s in steps)
        with pytest.raises(ValueError, match="unknown backend"):
            resolve_schedule((8, 8, 8), (2, 2, 2), methods="eig",
                             backend="nope")
