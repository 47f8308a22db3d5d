"""Sharded ops backend: pick_shard_mode edges, shard-aware schedule
resolution and plan JSON (in-process, no mesh needed), and end-to-end
multi-device execution parity (subprocess with 8 virtual CPU devices, per
the launch contract in dryrun.py — the main pytest process keeps its own
device view)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import TuckerConfig, TuckerPlan, mesh_from_spec, mesh_spec, plan
from repro.core.backend import get_backend, resolve_backend
from repro.core.distributed import pick_shard_mode
from repro.core.plan import resolve_schedule

REPO = Path(__file__).resolve().parent.parent


def run_in_subprocess(body: str):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import warnings; warnings.filterwarnings("ignore")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


# ---------------------------------------------------------------------------
# pick_shard_mode edge cases (pure function, no devices involved)
# ---------------------------------------------------------------------------

class TestPickShardMode:
    def test_picks_largest_divisible_mode(self):
        assert pick_shard_mode((24, 40, 16), exclude=0, n_shards=8) == 1

    def test_largest_mode_excluded_falls_to_next(self):
        # mode 0 is the largest but is being solved; next largest divisible
        assert pick_shard_mode((64, 16, 8), exclude=0, n_shards=8) == 1
        # largest mode excluded AND runner-up not divisible
        assert pick_shard_mode((64, 15, 8), exclude=0, n_shards=8) == 2

    def test_no_mode_divisible_replicates(self):
        assert pick_shard_mode((5, 7, 9), exclude=0, n_shards=4) is None
        # divisible mode exists but is the excluded one
        assert pick_shard_mode((8, 7, 9), exclude=0, n_shards=8) is None

    def test_late_shrunk_modes_fall_back_to_replication(self):
        # st-HOSVD end state: earlier modes already shrunk to tiny ranks
        assert pick_shard_mode((4, 5, 16), exclude=2, n_shards=8) is None

    def test_single_shard_always_shards(self):
        # n_shards=1 divides everything: largest non-excluded mode wins
        assert pick_shard_mode((3, 5, 7), exclude=2, n_shards=1) == 1


# ---------------------------------------------------------------------------
# Shard-aware schedule resolution (n_shards plumbing, per-device peak_bytes)
# ---------------------------------------------------------------------------

class TestShardedSchedule:
    def test_shard_modes_follow_the_shrinking_tensor(self):
        steps = resolve_schedule((24, 40, 16), (4, 5, 6), methods="eig",
                                 backend="sharded", n_shards=8)
        assert [s.shard_mode for s in steps] == [1, 2, None]
        assert [s.n_shards for s in steps] == [8, 8, 1]

    def test_peak_bytes_divide_by_shard_count(self):
        single = resolve_schedule((64, 48, 40), (8, 8, 8), methods="eig")
        shard = resolve_schedule((64, 48, 40), (8, 8, 8), methods="eig",
                                 backend="sharded", n_shards=8)
        s1, s8 = single[0], shard[0]
        # I/O slabs divide by 8; the replicated Gram scratch does not
        io1 = (s1.i_n * s1.j_n + s1.r_n * s1.j_n) * 4
        assert s8.peak_bytes == io1 // 8 + s1.i_n * s1.i_n * 4
        assert s8.peak_bytes < s1.peak_bytes

    def test_replicated_steps_keep_single_device_model(self):
        steps = resolve_schedule((5, 7, 9), (2, 2, 2), methods="eig",
                                 backend="sharded", n_shards=4)
        ref = resolve_schedule((5, 7, 9), (2, 2, 2), methods="eig")
        assert all(s.shard_mode is None and s.n_shards == 1 for s in steps)
        assert [s.peak_bytes for s in steps] == [s.peak_bytes for s in ref]

    def test_svd_steps_never_shard(self):
        steps = resolve_schedule((24, 40, 16), (4, 5, 6), methods="svd",
                                 backend="sharded", n_shards=8)
        assert all(s.shard_mode is None and s.n_shards == 1 for s in steps)

    def test_sharded_rejects_non_sthosvd_variants(self):
        with pytest.raises(ValueError, match="sthosvd"):
            resolve_schedule((8, 8, 8), (2, 2, 2), methods="eig",
                             variant="thosvd", backend="sharded", n_shards=4)

    def test_modestep_dict_roundtrip_keeps_shard_fields(self):
        from repro.core.plan import ModeStep
        steps = resolve_schedule((24, 40, 16), (4, 5, 6), methods="eig",
                                 backend="sharded", n_shards=8)
        for s in steps:
            assert ModeStep.from_dict(s.to_dict()) == s
        # pre-sharding plan files load with replicated defaults
        d = steps[0].to_dict()
        del d["shard_mode"], d["n_shards"]
        s = ModeStep.from_dict(d)
        assert s.shard_mode is None and s.n_shards == 1


# ---------------------------------------------------------------------------
# Backend registry + config validation (no multi-device mesh needed)
# ---------------------------------------------------------------------------

class TestShardedBackendRegistry:
    def test_registered_with_capabilities(self):
        b = get_backend("sharded")
        assert b.requires_mesh and not b.matricizes
        assert b.native_on("cpu") and b.native_on("tpu")

    def test_explicit_name_without_mesh_rejected(self):
        with pytest.raises(ValueError, match="requires a mesh"):
            resolve_backend("sharded")

    def test_auto_without_mesh_never_picks_sharded(self):
        assert resolve_backend("auto", platform="cpu").name == "matfree"

    def test_plan_without_mesh_rejected(self):
        with pytest.raises(ValueError, match="requires a mesh"):
            plan((8, 8, 8), jnp.float32,
                 TuckerConfig(ranks=(2, 2, 2), methods="eig", impl="sharded"))

    def test_shard_axis_must_be_a_mesh_axis(self):
        mesh = jax.make_mesh((1,), ("data",))
        with pytest.raises(ValueError, match="shard_axis"):
            TuckerConfig(ranks=(2, 2, 2), mesh=mesh, shard_axis="model",
                         impl="sharded")

    def test_mesh_with_single_device_impl_rejected(self):
        # a mesh the backend would silently ignore is a contradiction: the
        # user attached it precisely because one device is not enough
        mesh = jax.make_mesh((1,), ("data",))
        for impl in ("matfree", "explicit", "pallas"):
            with pytest.raises(ValueError, match="single device"):
                TuckerConfig(ranks=(2, 2, 2), mesh=mesh, impl=impl)
        # mesh-capable impls accept it
        TuckerConfig(ranks=(2, 2, 2), mesh=mesh, impl="sharded")
        TuckerConfig(ranks=(2, 2, 2), mesh=mesh, impl="auto")

    def test_engine_drops_mesh_for_single_device_pin(self):
        from repro.serve import TuckerBatchEngine
        mesh = jax.make_mesh((1,), ("data",))
        eng = TuckerBatchEngine(impl="matfree", mesh=mesh)
        cfg = eng._pinned(TuckerConfig(ranks=(2, 2, 2), methods="eig"))
        assert cfg.impl == "matfree" and cfg.mesh is None
        # no explicit impl: a mesh pins the sharded backend
        eng = TuckerBatchEngine(mesh=mesh)
        cfg = eng._pinned(TuckerConfig(ranks=(2, 2, 2), methods="eig"))
        assert cfg.impl == "sharded" and cfg.mesh is mesh

    def test_sharded_variant_guard_at_plan_time(self):
        mesh = jax.make_mesh((1,), ("data",))
        with pytest.raises(ValueError, match="sthosvd"):
            plan((8, 8, 8), jnp.float32,
                 TuckerConfig(ranks=(2, 2, 2), methods="eig", variant="thosvd",
                              impl="sharded", mesh=mesh))


# ---------------------------------------------------------------------------
# Mesh spec + plan JSON roundtrip (1-device mesh works on any host)
# ---------------------------------------------------------------------------

class TestMeshSerialization:
    def test_mesh_spec_roundtrip(self):
        mesh = jax.make_mesh((1,), ("data",))
        spec = mesh_spec(mesh)
        assert spec == {"axis_names": ["data"], "shape": [1]}
        rebuilt = mesh_from_spec(spec)
        assert rebuilt is not None
        assert rebuilt.axis_names == ("data",) and rebuilt.shape["data"] == 1
        assert mesh_spec(None) is None and mesh_from_spec(None) is None

    def test_oversized_spec_degrades_to_none(self):
        assert mesh_from_spec(
            {"axis_names": ["data"], "shape": [10 ** 6]}) is None

    def test_config_dict_roundtrip_with_mesh(self):
        mesh = jax.make_mesh((1,), ("data",))
        c = TuckerConfig(ranks=(2, 2, 2), methods="eig", impl="sharded",
                         mesh=mesh, shard_axis="data")
        c2 = TuckerConfig.from_dict(c.to_dict())
        assert c2.shard_axis == "data" and c2.impl == "sharded"
        assert mesh_spec(c2.mesh) == mesh_spec(mesh)

    def test_plan_json_roundtrip_and_execute_on_one_device_mesh(self, tmp_path):
        import numpy as np
        mesh = jax.make_mesh((1,), ("data",))
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal((8, 7, 6)), jnp.float32)
        cfg = TuckerConfig(ranks=(2, 3, 2), methods="eig", impl="sharded",
                           mesh=mesh)
        p = plan(x.shape, x.dtype, cfg)
        assert p.backend == "sharded"
        path = tmp_path / "p.json"
        p.save(path)
        p2 = TuckerPlan.load(path)
        assert p2.schedule == p.schedule
        assert p2.config.shard_axis == cfg.shard_axis
        assert mesh_spec(p2.config.mesh) == mesh_spec(mesh)
        r1, r2 = p.execute(x), p2.execute(x)
        np.testing.assert_allclose(np.asarray(r1.tucker.core),
                                   np.asarray(r2.tucker.core),
                                   rtol=1e-6, atol=1e-6)
        # a 1-device mesh is degenerate sharding: parity with plain matfree
        ref = plan(x.shape, x.dtype,
                   TuckerConfig(ranks=(2, 3, 2), methods="eig")).execute(x)
        np.testing.assert_allclose(np.asarray(r1.tucker.reconstruct()),
                                   np.asarray(ref.tucker.reconstruct()),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# End-to-end on 8 virtual devices (subprocess)
# ---------------------------------------------------------------------------

def test_sharded_plan_matches_matfree_all_methods():
    """Acceptance: plan(..., impl="sharded") and impl="auto"+mesh execute on
    an 8-device mesh with results allclose to single-device matfree, zero
    recompiles on plan reuse."""
    run_in_subprocess("""
        from repro.core import TuckerConfig, plan, tensor_ops as T
        from repro.core import api as api_mod
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        G = rng.standard_normal((4,5,6))
        Us = [np.linalg.qr(rng.standard_normal((d, r)))[0]
              for d, r in zip((24,40,16),(4,5,6))]
        X = T.reconstruct(jnp.asarray(G, jnp.float32),
                          [jnp.asarray(u, jnp.float32) for u in Us])
        X = X + 0.001*jnp.asarray(rng.standard_normal(X.shape), jnp.float32)
        for methods in ("eig", "als", "auto"):
            ref = plan(X.shape, X.dtype,
                       TuckerConfig(ranks=(4,5,6), methods=methods)).execute(X)
            p = plan(X.shape, X.dtype,
                     TuckerConfig(ranks=(4,5,6), methods=methods,
                                  impl="sharded", mesh=mesh))
            assert p.backend == "sharded"
            assert p.schedule[0].n_shards == 8, p.schedule
            res = p.execute(X)
            # same frozen solver schedule on both sides
            assert res.methods == ref.methods, (res.methods, ref.methods)
            np.testing.assert_allclose(np.asarray(res.tucker.reconstruct()),
                                       np.asarray(ref.tucker.reconstruct()),
                                       rtol=2e-3, atol=2e-3)
            e1 = float(ref.tucker.rel_error(X)); e2 = float(res.tucker.rel_error(X))
            assert abs(e1 - e2) < 1e-4, (methods, e1, e2)
            # factor subspace parity, sign/rotation-invariant
            for a, b in zip(ref.tucker.factors, res.tucker.factors):
                pa, pb = a @ a.T, b @ b.T
                assert float(jnp.abs(pa - pb).max()) < 1e-3, methods
        # impl="auto" with a mesh resolves to sharded
        p = plan(X.shape, X.dtype, TuckerConfig(ranks=(4,5,6), methods="eig",
                                                impl="auto", mesh=mesh))
        assert p.backend == "sharded"
        # zero recompiles / selections on reuse
        api_mod.clear_sweep_cache()
        p = plan(X.shape, X.dtype, TuckerConfig(ranks=(4,5,6), methods="eig",
                                                impl="sharded", mesh=mesh))
        for i in range(3):
            p.execute(X + float(i))
        assert api_mod.CACHE_STATS == {"builds": 1, "hits": 2, "traces": 1}, \
            api_mod.CACHE_STATS
        print("OK")
    """)


def test_sharded_plan_json_roundtrip_rebuilds_mesh():
    run_in_subprocess("""
        from repro.core import TuckerConfig, TuckerPlan, mesh_spec, plan
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(1)
        X = jnp.asarray(rng.standard_normal((24, 40, 16)), jnp.float32)
        p = plan(X.shape, X.dtype,
                 TuckerConfig(ranks=(4,5,6), methods="eig", impl="sharded",
                              mesh=mesh))
        p2 = TuckerPlan.from_json(p.to_json())
        assert p2.schedule == p.schedule
        assert [s.shard_mode for s in p2.schedule] == [1, 2, None]
        assert mesh_spec(p2.config.mesh) == {"axis_names": ["data"],
                                             "shape": [8]}
        r1, r2 = p.execute(X), p2.execute(X)
        np.testing.assert_allclose(np.asarray(r1.tucker.core),
                                   np.asarray(r2.tucker.core),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)


def test_distributed_wrapper_records_real_wall_clock():
    """sthosvd_distributed, and record=True on sharded and mode-parallel
    plans, time every step and agree with the compiled sweep."""
    run_in_subprocess("""
        from repro.core import TuckerConfig, plan
        from repro.core.distributed import sthosvd_distributed
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(2)
        X = jnp.asarray(rng.standard_normal((24, 40, 16)), jnp.float32)
        for methods in ("eig", "als", "auto"):
            res = sthosvd_distributed(X, (4, 5, 6), mesh, methods=methods)
            assert all(t.seconds > 0 for t in res.trace), \
                (methods, [t.seconds for t in res.trace])
            assert all(t.backend == "sharded" for t in res.trace)
            assert res.tucker.core.shape == (4, 5, 6)
        for methods in ("eig", "als"):
            for mp in ("off", 2):
                p = plan(X.shape, X.dtype,
                         TuckerConfig(ranks=(4, 5, 6), methods=methods,
                                      impl="sharded", mesh=mesh,
                                      mode_parallel=mp))
                assert (mp == 2) == any(s.group is not None
                                        for s in p.schedule)
                ref = p.execute(X)
                rec = p.execute(X, record=True)
                assert len(rec.trace) == len(p.schedule)
                assert all(t.seconds > 0 for t in rec.trace), \
                    (methods, mp, [t.seconds for t in rec.trace])
                np.testing.assert_allclose(
                    np.asarray(rec.tucker.reconstruct()),
                    np.asarray(ref.tucker.reconstruct()),
                    rtol=1e-5, atol=1e-5)
        print("OK")
    """)


def test_engine_executes_sharded_with_mesh():
    run_in_subprocess("""
        from repro.core import TuckerConfig
        from repro.serve import TuckerBatchEngine, TuckerRequest
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(3)
        eng = TuckerBatchEngine(mesh=mesh)
        cfg = TuckerConfig(ranks=(4, 5, 6), methods="eig")
        reqs = [TuckerRequest(
                    x=jnp.asarray(rng.standard_normal((24, 40, 16)),
                                  jnp.float32),
                    config=cfg, rid=s) for s in range(4)]
        eng.run(reqs)
        assert all(r.result is not None for r in reqs)
        assert eng.stats["backends"] == {"sharded": 4}, eng.stats
        assert eng.stats["plans_built"] == 1    # one plan for the group
        assert eng.stats["batches"] == 1
        for r in reqs:
            assert float(r.result.tucker.rel_error(r.x)) < 1.0
        print("OK")
    """)


# ---------------------------------------------------------------------------
# Plan-time schedule search against the PER-DEVICE peak model
# ---------------------------------------------------------------------------

class TestShardedScheduleSearch:
    def test_opt_schedule_resolves_with_per_device_peaks(self):
        # no mesh needed: resolve_schedule(n_shards=8) is pure bookkeeping
        steps = resolve_schedule((64, 48, 40), (8, 6, 5), methods="eig",
                                 mode_order="opt", backend="sharded",
                                 n_shards=8)
        assert sorted(s.mode for s in steps) == [0, 1, 2]
        assert steps[0].n_shards == 8    # first step shards the full tensor

    def test_per_device_cap_feasible_only_when_sharded(self):
        from repro.core.schedule_opt import MemoryCapError, optimize_schedule

        shape, ranks = (64, 48, 40), (8, 6, 5)
        single = optimize_schedule(shape, ranks, methods=["eig"] * 3)
        # tightest single-device bottleneck: any order's worst step io
        steps1 = resolve_schedule(shape, ranks, methods="eig",
                                  mode_order="opt")
        cap = max(s.peak_bytes for s in steps1) // 4
        with pytest.raises(MemoryCapError):
            optimize_schedule(shape, ranks, methods=["eig"] * 3,
                              memory_cap_bytes=cap)
        # the same cap fits once the io slabs divide over 8 devices
        sharded = optimize_schedule(shape, ranks, methods=["eig"] * 3,
                                    n_shards=8, memory_cap_bytes=cap)
        assert sharded.order is not None
        steps8 = resolve_schedule(shape, ranks, methods="eig",
                                  mode_order="opt", backend="sharded",
                                  n_shards=8, memory_cap_bytes=cap)
        assert all(s.peak_bytes <= cap for s in steps8)

    def test_opt_plan_executes_on_mesh(self):
        run_in_subprocess("""
            from repro.core import TuckerConfig, plan, tensor_ops as T
            mesh = jax.make_mesh((8,), ("data",))
            rng = np.random.default_rng(0)
            G = rng.standard_normal((4, 5, 6))
            Us = [np.linalg.qr(rng.standard_normal((d, r)))[0]
                  for d, r in zip((24, 40, 16), (4, 5, 6))]
            X = T.reconstruct(jnp.asarray(G, jnp.float32),
                              [jnp.asarray(u, jnp.float32) for u in Us])
            ref = plan(X.shape, X.dtype,
                       TuckerConfig(ranks=(4, 5, 6), methods="eig")).execute(X)
            p = plan(X.shape, X.dtype,
                     TuckerConfig(ranks=(4, 5, 6), methods="eig",
                                  mode_order="opt", impl="sharded",
                                  mesh=mesh,
                                  memory_cap_bytes=64 * 1024 * 1024))
            assert p.backend == "sharded"
            res = p.execute(X)
            err = float(res.tucker.rel_error(X))
            ref_err = float(ref.tucker.rel_error(X))
            assert abs(err - ref_err) < 1e-3, (err, ref_err)
            # sharded sweeps must never donate (shard_map aliasing guard)
            assert p.donates is False
        """)

    def test_distributed_wrapper_takes_mode_order_and_cap(self):
        run_in_subprocess("""
            from repro.core.distributed import sthosvd_distributed
            from repro.core.schedule_opt import MemoryCapError
            mesh = jax.make_mesh((8,), ("data",))
            X = jnp.asarray(np.random.default_rng(0)
                            .standard_normal((24, 40, 16)), jnp.float32)
            res = sthosvd_distributed(X, (4, 5, 6), mesh, methods="eig",
                                      mode_order="opt")
            assert float(res.tucker.rel_error(X)) < 1.0
            try:
                sthosvd_distributed(X, (4, 5, 6), mesh, methods="eig",
                                    memory_cap_bytes=1000)
            except MemoryCapError as e:
                assert "bytes" in str(e)
            else:
                raise AssertionError("cap should have been infeasible")
        """)


# ---------------------------------------------------------------------------
# Plan derivation (for_shape) on sharded plans — the serve-layer reuse hook
# ---------------------------------------------------------------------------

def test_for_shape_rederives_sharded_plans():
    run_in_subprocess("""
        from repro.core import TuckerConfig, mesh_spec, plan
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(4)
        base = plan((24, 40, 16), jnp.float32,
                    TuckerConfig(ranks=(4, 5, 6), methods="eig",
                                 impl="sharded", mesh=mesh))
        assert [s.shard_mode for s in base.schedule] == [1, 2, None]

        # derived plan keeps the mesh and matches a direct plan exactly
        d = base.for_shape((24, 40, 15))
        assert mesh_spec(d.config.mesh) == mesh_spec(mesh)
        direct = plan((24, 40, 15), jnp.float32, base.config)
        assert d.schedule == direct.schedule
        assert d.backend == "sharded"

        # shard participation RE-resolves for the new dims: with mode 0
        # already shrunk to rank 4, neither 4 nor 15 divides over the 8
        # devices, so the mode-1 solve replicates instead of sharding mode 2
        assert [s.shard_mode for s in d.schedule] == [1, None, None]

        X = jnp.asarray(rng.standard_normal((24, 40, 15)), jnp.float32)
        r1, r2 = d.execute(X), direct.execute(X)
        np.testing.assert_allclose(np.asarray(r1.tucker.core),
                                   np.asarray(r2.tucker.core),
                                   rtol=1e-5, atol=1e-5)

        # keep_methods pins the bucket plan's solvers and sweep order
        auto = plan((24, 40, 16), jnp.float32,
                    TuckerConfig(ranks=(4, 5, 6), methods=("als", "eig",
                                                           "als"),
                                 impl="sharded", mesh=mesh))
        pinned = auto.for_shape((24, 40, 15), keep_methods=True)
        assert pinned.methods == auto.methods
        assert [s.mode for s in pinned.schedule] == \\
            [s.mode for s in auto.schedule]

        # same-shape derivation is the identity (no replanning)
        assert base.for_shape((24, 40, 16)) is base
        print("OK")
    """)
