"""Fault-tolerance suite: failure taxonomy, fallback ladder, serve isolation.

Faults are injected through the deterministic chaos harness
(``src/repro/chaos``) at named seams; every test asserts one of the two
allowed outcomes — the fault is RECOVERED (degraded but correct results)
or CLASSIFIED (a ``TuckerError`` subclass naming what went wrong).  An
unclassified exception escaping ``plan.execute`` or ``TuckerService.poll``
is always a failure here.

Run under ``ATUCKER_CHAOS=numerical|oom|serve-poison`` the env-profile
test additionally exercises the shipped profiles end to end (CI's
``resilience`` job does exactly that, three times).
"""

import os
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro import chaos
from repro.core import (CancelledError, DeadlineError, InputError,
                        MemoryCapError, NumericalError, ResourceError,
                        TuckerConfig, TuckerError, check_finite,
                        classify_exception, coerce_exception, plan)
from repro.serve import BucketPolicy, TuckerService
from repro.serve.service import _Breaker
from tests._hypothesis_compat import given, settings, st

F32 = "float32"


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.reset()


def _rand(shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(np.float32)


# -- taxonomy -----------------------------------------------------------------

class TestTaxonomy:
    def test_hierarchy_is_dual(self):
        # every class keeps its pre-taxonomy base so old call sites work
        assert issubclass(InputError, ValueError)
        assert issubclass(NumericalError, FloatingPointError)
        assert issubclass(DeadlineError, TimeoutError)
        assert issubclass(MemoryCapError, ResourceError)
        assert issubclass(MemoryCapError, ValueError)
        for cls in (InputError, NumericalError, ResourceError,
                    DeadlineError, CancelledError):
            assert issubclass(cls, TuckerError)
            assert issubclass(cls, RuntimeError)

    def test_classify_markers(self):
        assert isinstance(
            classify_exception(RuntimeError("RESOURCE_EXHAUSTED: oom")),
            ResourceError)
        assert isinstance(classify_exception(MemoryError()), ResourceError)
        assert isinstance(
            classify_exception(RuntimeError("Cholesky failed: matrix is "
                                            "not positive definite")),
            NumericalError)
        assert isinstance(classify_exception(ZeroDivisionError()),
                          NumericalError)
        assert classify_exception(KeyError("bug")) is None

    def test_classify_passthrough_and_cause(self):
        e = NumericalError("already classified")
        assert classify_exception(e) is e
        src = RuntimeError("Out of memory while allocating")
        wrapped = classify_exception(src)
        assert wrapped.__cause__ is src

    def test_coerce_is_total(self):
        e = coerce_exception(KeyError("bug"))
        assert isinstance(e, TuckerError)
        assert "unclassified" in str(e)
        r = ResourceError("x")
        assert coerce_exception(r) is r

    def test_check_finite_names_mode(self):
        x = _rand((6, 5, 4))
        x[:, 3, :] = np.nan     # a full mode-1 slice of NaNs
        with pytest.raises(InputError, match="mode 1"):
            check_finite(x, name="input")
        assert check_finite(_rand((4, 4)), name="input") is None


# -- solver guards ------------------------------------------------------------

class TestSolverGuards:
    def test_als_survives_rank_deficient_gram(self):
        # an exactly rank-1 tensor makes every Gram singular; the jittered
        # re-regularization ladder in _spd_inverse must keep ALS finite
        a, b, c = _rand(12, 1), _rand(10, 2), _rand(8, 3)
        x = np.einsum("i,j,k->ijk", a, b, c)
        cfg = TuckerConfig(ranks=(3, 3, 3), methods="als")
        res = plan(x.shape, F32, cfg).execute(x, validate="finite")
        assert np.all(np.isfinite(np.asarray(res.tucker.core)))

    def test_solver_breakdown_is_classified(self):
        # poison an eager (per-step) solve output: the run_schedule guard
        # must classify it as NumericalError, not let NaNs flow downstream
        from repro.core.plan import run_schedule
        from repro.core.api import plan as make_plan
        chaos.install([chaos.Rule(seam="solve_out", action="nan", at=0,
                                  times=1)])
        x = _rand((10, 9, 8))
        p = make_plan(x.shape, F32, TuckerConfig(ranks=(3, 3, 3)))
        with pytest.raises(NumericalError, match="non-finite"):
            run_schedule(jnp.asarray(x), p.schedule, sequential=True,
                         block_until_ready=True)


# -- execute-time fallback ladder --------------------------------------------

class TestFallbackLadder:
    def test_als_to_eig_on_poisoned_sweep(self):
        # fused sweep output NaN once -> ladder hops als->eig and recovers
        chaos.install([chaos.Rule(seam="sweep_out", action="nan", at=0,
                                  times=1)])
        x = _rand((12, 10, 8), seed=1)
        cfg = TuckerConfig(ranks=(3, 3, 3), methods="als")
        res = plan(x.shape, F32, cfg).execute(x, validate="finite")
        assert np.all(np.isfinite(np.asarray(res.tucker.core)))
        assert sum(chaos.fired().values()) >= 1

    def test_oom_hops_to_undonated(self):
        chaos.install([chaos.Rule(seam="sweep", action="oom", at=0,
                                  times=1)])
        x = _rand((12, 10, 8), seed=2)
        res = plan(x.shape, F32, TuckerConfig(ranks=(3, 3, 3))).execute(x)
        assert np.all(np.isfinite(np.asarray(res.tucker.core)))
        assert sum(chaos.fired().values()) == 1

    def test_persistent_oom_is_classified_and_bounded(self):
        # an OOM that never goes away must exhaust the (bounded) ladder and
        # surface as ResourceError — not loop forever, not escape raw
        chaos.install([chaos.Rule(seam="sweep", action="oom", times=None)])
        x = _rand((12, 10, 8), seed=3)
        p = plan(x.shape, F32, TuckerConfig(ranks=(3, 3, 3)))
        with pytest.raises(ResourceError):
            p.execute(x)
        assert sum(chaos.fired().values()) <= 4   # one attempt per rung, no retry storms

    def test_kernel_lowering_error_raises_without_hop(self, monkeypatch):
        # compiled (not interpreted) Pallas kernels cannot lower on CPU: a
        # real lowering failure, which must surface as KernelError instead
        # of silently re-planning the sweep onto matfree
        from repro import obs
        from repro.core import KernelError
        from repro.kernels import ops
        from repro.obs import metrics
        assert isinstance(classify_exception(RuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel")), KernelError)
        monkeypatch.setattr(ops, "_default_interpret", lambda: False)
        hops = metrics.REGISTRY.counter("atucker_fallback_hops_total", "")
        before = hops.value(hop="pallas_to_matfree", backend="pallas")
        x = _rand((11, 13, 9), seed=6)
        p = plan(x.shape, F32, TuckerConfig(ranks=(3, 3, 3), impl="pallas",
                                            methods="eig"))
        with obs.capture() as buf, pytest.raises(KernelError):
            p.execute(x)
        assert not [e for e in buf.events() if e.get("kind") == "fallback"]
        assert hops.value(hop="pallas_to_matfree", backend="pallas") == before

    def test_nan_input_rejected_by_validate(self):
        x = _rand((8, 8, 8), seed=4)
        x[2, :, :] = np.inf
        p = plan(x.shape, F32, TuckerConfig(ranks=(3, 3, 3)))
        with pytest.raises(InputError, match="mode 0"):
            p.execute(x, validate="finite")

    def test_sketch_miss_hops_to_eig(self):
        # incompressible input + a tiny capped sketch grid: the adaptive
        # pass misses its error target, and the plan refines with exact
        # eig solves instead of serving the miss silently
        chaos.reset()
        x = _rand((16, 12, 10), seed=5)
        cfg = TuckerConfig(error_target=0.05, rank_grid=(2,))
        res = plan(x.shape, F32, cfg).execute(x)
        assert np.all(np.isfinite(np.asarray(res.tucker.core)))
        assert np.asarray(res.tucker.core).shape == (2, 2, 2)
        assert res.error_bound is not None     # honest about the miss


# -- chaos harness ------------------------------------------------------------

class TestChaosHarness:
    def test_schedule_at_and_times(self):
        chaos.install([chaos.Rule(seam="s", action="raise", at=1, times=1)])
        chaos.fire("s")                       # hit 0: not due
        with pytest.raises(chaos.ChaosFault):
            chaos.fire("s")                   # hit 1: due
        chaos.fire("s")                       # times=1 budget spent
        assert sum(chaos.fired().values()) == 1

    def test_match_filters_context(self):
        chaos.install([chaos.Rule(seam="s", action="raise", times=None,
                                  match={"rid": 2})])
        chaos.fire("s", rid=0)
        chaos.fire("s", rid=1)
        with pytest.raises(chaos.ChaosFault):
            chaos.fire("s", rid=2)

    def test_probability_is_seed_deterministic(self):
        def pattern(seed):
            chaos.reset()
            chaos.install([chaos.Rule(seam="s", action="raise", p=0.5,
                                      times=None, seed=seed)])
            out = []
            for _ in range(32):
                try:
                    chaos.fire("s")
                    out.append(0)
                except chaos.ChaosFault:
                    out.append(1)
            return out

        assert pattern(7) == pattern(7)
        assert pattern(7) != pattern(8)   # astronomically unlikely to tie

    def test_synthetic_oom_classifies_as_resource(self):
        chaos.install([chaos.Rule(seam="s", action="oom", times=1)])
        with pytest.raises(chaos.SyntheticOOM) as ei:
            chaos.fire("s")
        assert isinstance(classify_exception(ei.value), ResourceError)

    def test_profiles_install_and_bad_name_is_loud(self):
        chaos.install_profile("numerical")
        assert chaos.active()
        with pytest.raises(ValueError, match="numerical"):
            chaos.install_profile("no-such-profile")


# -- serve-side isolation -----------------------------------------------------

def _mask_service(**kw):
    kw.setdefault("policy", BucketPolicy(grid=8, pad_mode="mask",
                                         wave_slots=8))
    kw.setdefault("max_queue", 64)
    return TuckerService(**kw)


_CFG = TuckerConfig(ranks=(3, 3, 3))


def _job_shapes(n):
    # mixed true shapes in one (8, 8, 8) mask bucket (>=1 padded member,
    # so waves take the fused path)
    return [(8 - (i % 2), 8, 8 - (i % 3)) for i in range(n)]


def _run_stream(svc, shapes, **submit_kw):
    tickets = [svc.submit(_rand(s, seed=100 + i), _CFG, rid=i, **submit_kw)
               for i, s in enumerate(shapes)]
    svc.drain()
    out = []
    for t in tickets:
        try:
            out.append(svc.poll(t))
        except Exception as e:  # noqa: BLE001 - collected for assertions
            out.append(e)
    return out


class TestServeIsolation:
    def test_deadline_expires_prewave(self):
        svc = _mask_service()
        t = svc.submit(_rand((7, 8, 8)), _CFG, deadline_s=0.01)
        time.sleep(0.05)
        svc.drain()
        with pytest.raises(DeadlineError):
            svc.poll(t)
        assert svc.stats()["resilience"]["deadline_expired"] == 1

    def test_deadline_validation(self):
        svc = _mask_service()
        with pytest.raises(ValueError):
            svc.submit(_rand((7, 8, 8)), _CFG, deadline_s=0.0)

    def test_cancel_before_dispatch(self):
        svc = _mask_service()
        t0 = svc.submit(_rand((7, 8, 8), seed=1), _CFG)
        t1 = svc.submit(_rand((8, 8, 7), seed=2), _CFG)
        assert svc.cancel(t0) is True
        svc.drain()
        with pytest.raises(CancelledError):
            svc.poll(t0)
        assert svc.poll(t1) is not None
        assert svc.cancel(t1) is False      # already completed
        s = svc.stats()
        assert s["resilience"]["cancelled"] == 1
        assert s["requests"] == 1

    def test_submit_rejects_nonfinite_input(self):
        svc = _mask_service()
        x = _rand((7, 8, 8))
        x[:, 2, :] = np.nan
        with pytest.raises(InputError, match="mode 1"):
            svc.submit(x, _CFG)
        # trusted traffic can opt out of the admission check
        t = svc.submit(x, _CFG, validate="none")
        svc.drain()
        with pytest.raises(TuckerError):    # classified downstream instead
            svc.poll(t)

    def test_poisoned_job_fails_alone_others_bitwise_clean(self):
        shapes = _job_shapes(5)
        clean = _run_stream(_mask_service(), shapes)
        assert all(not isinstance(r, Exception) for r in clean)
        # rid 2 raises on EVERY attempt (dispatch, bisection, isolation)
        chaos.install([chaos.Rule(seam="wave_job", action="raise",
                                  times=None, match={"rid": 2},
                                  message="synthetic poisoned request")])
        poisoned = _run_stream(_mask_service(), shapes)
        assert isinstance(poisoned[2], TuckerError)
        for i in (0, 1, 3, 4):
            assert not isinstance(poisoned[i], Exception)
            assert np.array_equal(np.asarray(clean[i].tucker.core),
                                  np.asarray(poisoned[i].tucker.core))
            for uc, up in zip(clean[i].tucker.factors,
                              poisoned[i].tucker.factors):
                assert np.array_equal(np.asarray(uc), np.asarray(up))

    @settings(max_examples=5, deadline=None)
    @given(n=st.integers(2, 6), poison=st.integers(0, 5))
    def test_bisection_bitwise_property(self, n, poison):
        poison = poison % n
        shapes = _job_shapes(n)
        chaos.reset()
        clean = _run_stream(_mask_service(), shapes)
        chaos.install([chaos.Rule(seam="wave_job", action="raise",
                                  times=None, match={"rid": poison})])
        got = _run_stream(_mask_service(), shapes)
        chaos.reset()
        assert isinstance(got[poison], TuckerError)
        for i in range(n):
            if i == poison:
                continue
            assert np.array_equal(np.asarray(clean[i].tucker.core),
                                  np.asarray(got[i].tucker.core))

    def test_nan_lane_quarantined_and_recovered(self):
        # transient data poison in ONE fused lane: that lane re-derives in
        # isolation from the intact input; nobody else re-runs
        shapes = _job_shapes(4)
        chaos.install([chaos.Rule(seam="wave_job_data", action="nan",
                                  times=1, match={"rid": 1})])
        svc = _mask_service()
        out = _run_stream(svc, shapes)
        assert all(not isinstance(r, Exception) for r in out)
        assert all(np.all(np.isfinite(np.asarray(r.tucker.core)))
                   for r in out)
        res = svc.stats()["resilience"]
        assert res["quarantined"] >= 1
        assert res["recovered"] >= 1

    def test_retry_budget_recovers_transient_fault(self):
        # the fault persists through dispatch + bisection + isolation of
        # wave 1 (3 firings), then goes away; retries=1 re-enqueues the job
        chaos.install([chaos.Rule(seam="wave_job", action="raise", times=3,
                                  match={"rid": 0})])
        svc = _mask_service()
        t = svc.submit(_rand((7, 8, 8)), _CFG, rid=0, retries=1)
        svc.drain()
        assert svc.poll(t) is not None
        assert svc.stats()["resilience"]["retried"] == 1
        assert sum(chaos.fired().values()) == 3

    def test_retry_budget_exhausts_to_classified(self):
        chaos.install([chaos.Rule(seam="wave_job", action="raise",
                                  times=None, match={"rid": 0})])
        svc = _mask_service()
        t = svc.submit(_rand((7, 8, 8)), _CFG, rid=0, retries=2)
        svc.drain()
        with pytest.raises(TuckerError):
            svc.poll(t)
        assert svc.stats()["resilience"]["retried"] == 2

    def test_breaker_trips_isolates_and_recovers(self):
        # every fused wave "fails" (recovery succeeds, but the fused path
        # itself keeps breaking) -> breaker opens after 2 waves; requests
        # keep completing through bisection and then isolation
        chaos.install([chaos.Rule(seam="wave", action="raise", times=None)])
        svc = _mask_service(breaker_threshold=2, breaker_cooldown_s=0.05)
        for wave in range(3):
            shapes = _job_shapes(2)
            out = _run_stream(svc, shapes)
            assert all(not isinstance(r, Exception) for r in out)
        s = svc.stats()
        assert s["resilience"]["breaker_trips"] == 1
        assert s["resilience"]["isolated_waves"] >= 1
        assert svc.health()["status"] == "degraded"
        # fault clears; after the cooldown one fused probe re-closes it
        chaos.reset()
        time.sleep(0.06)
        out = _run_stream(svc, _job_shapes(2))
        assert all(not isinstance(r, Exception) for r in out)
        s = svc.stats()
        assert s["resilience"]["probe_waves"] >= 1
        assert s["resilience"]["breakers_open"] == 0
        assert svc.health()["status"] == "ok"

    def test_stop_force_abandons_with_classified_error(self):
        chaos.install([chaos.Rule(seam="wave", action="slow", times=None,
                                  delay_s=0.3)])
        svc = _mask_service(breaker_cooldown_s=60.0)
        svc.start()
        tickets = [svc.submit(_rand(s, seed=i), _CFG)
                   for i, s in enumerate(_job_shapes(6))]
        time.sleep(0.05)
        svc.stop(force=True, join_timeout=5.0)
        for t in tickets:
            assert t._job.event.wait(timeout=5.0)
            with pytest.raises((ResourceError, TuckerError)):
                svc.poll(t)

    def test_stop_warns_naming_wedged_bucket(self):
        chaos.install([chaos.Rule(seam="wave", action="slow", times=None,
                                  delay_s=1.5)])
        svc = _mask_service()
        svc.start()
        worker = svc._thread
        svc.submit(_rand((7, 8, 8)), _CFG)
        time.sleep(0.3)          # let the worker enter the slow wave
        with pytest.warns(RuntimeWarning, match="8x8x8"):
            svc.stop(drain=False, force=True, join_timeout=0.2)
        # the daemonic worker was abandoned mid-wave; reap it so it is not
        # still driving the device when the interpreter tears down
        worker.join(timeout=10.0)
        assert not worker.is_alive()

    def test_worker_death_fails_jobs_classified(self):
        svc = _mask_service()
        t = svc.submit(_rand((7, 8, 8)), _CFG)
        chaos.install([chaos.Rule(seam="worker", action="raise", times=1)])
        svc.start()
        assert t._job.event.wait(timeout=10.0)
        with pytest.raises(ResourceError, match="worker died"):
            svc.poll(t)
        assert svc.health()["status"] == "unhealthy"

    def test_no_unclassified_escape_under_poison_profile(self):
        chaos.install_profile("serve-poison")
        out = _run_stream(_mask_service(), _job_shapes(5))
        for i, r in enumerate(out):
            if isinstance(r, Exception):
                assert isinstance(r, TuckerError), (
                    f"rid {i}: unclassified {type(r).__name__} escaped")
            else:
                assert np.all(np.isfinite(np.asarray(r.tucker.core)))
        assert isinstance(out[2], TuckerError)   # the profile poisons rid 2


class TestBreakerUnit:
    def test_concurrent_failures_trip_exactly_once(self):
        br = _Breaker(threshold=1, cooldown_s=10.0)
        lock = threading.RLock()
        start = threading.Barrier(8)
        def hammer():
            start.wait()
            for _ in range(200):
                with lock:
                    br.on_result(False, 0.0)
        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
            assert not th.is_alive(), "breaker hammer deadlocked"
        assert br.trips == 1
        assert br.state == "open"

    def test_probe_cycle(self):
        br = _Breaker(threshold=2, cooldown_s=1.0)
        assert br.route(0.0) == "fused"
        br.on_result(False, 0.0)
        assert br.on_result(False, 0.0) is True   # trip
        assert br.route(0.5) == "isolated"        # cooling down
        assert br.route(1.5) == "probe"           # cooldown elapsed
        assert br.route(1.6) == "isolated"        # probe slot claimed
        br.on_probe(False, 1.7)                   # probe failed: reopen
        assert br.reopens == 1 and br.trips == 1
        assert br.route(3.0) == "probe"
        br.on_probe(True, 3.1)
        assert br.state == "closed"
        assert br.route(3.2) == "fused"


# -- shipped profiles end to end (CI runs these under ATUCKER_CHAOS) ---------

PROFILE = os.environ.get("ATUCKER_CHAOS")


@pytest.mark.skipif(PROFILE is None,
                    reason="set ATUCKER_CHAOS=numerical|oom|serve-poison")
def test_env_profile_recovers_or_classifies():
    chaos.install_profile(PROFILE)   # the autouse fixture cleared the env rules
    if PROFILE == "serve-poison":
        out = _run_stream(_mask_service(), _job_shapes(5))
        for r in out:
            assert not isinstance(r, Exception) or isinstance(r, TuckerError)
        assert isinstance(out[2], TuckerError)
    else:
        x = _rand((12, 10, 8), seed=11)
        res = plan(x.shape, F32, TuckerConfig(ranks=(3, 3, 3))).execute(
            x, validate="finite")
        assert np.all(np.isfinite(np.asarray(res.tucker.core)))
        assert sum(chaos.fired().values()) >= 1
