"""t-HOSVD and HOOI variants (paper §II-B / future-work §VIII)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sthosvd_eig, tensor_ops as T
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


class TestTHOSVD:
    @pytest.mark.parametrize("methods", ["eig", "als"])
    def test_exact_recovery(self, methods):
        x = lowrank((12, 10, 8), (3, 3, 2))
        res = thosvd(x, (3, 3, 2), methods=methods)
        assert float(res.tucker.rel_error(x)) < 1e-4

    def test_orthonormal_and_auto(self):
        x = lowrank((12, 10, 8), (3, 3, 2), noise=0.05)
        res = thosvd(x, (3, 3, 2), methods="auto")
        for u in res.tucker.factors:
            np.testing.assert_allclose(np.asarray(u.T @ u),
                                       np.eye(u.shape[1]), atol=2e-3)
        assert float(res.tucker.rel_error(x)) < 0.12


class TestHOOI:
    def test_refines_sthosvd(self):
        """HOOI error ≤ st-HOSVD's, its own init (monotone refinement)."""
        x = lowrank((14, 12, 10), (3, 3, 3), noise=0.3)
        e0 = float(sthosvd_eig(x, (3, 3, 3)).tucker.rel_error(x))
        res = hooi(x, (3, 3, 3), n_iters=2, methods="eig")
        e1 = float(res.tucker.rel_error(x))
        assert e1 <= e0 + 1e-5

    def test_exact_recovery(self):
        x = lowrank((10, 9, 8), (2, 3, 2))
        res = hooi(x, (2, 3, 2), n_iters=1, methods="eig")
        assert float(res.tucker.rel_error(x)) < 1e-4

    def test_auto_selector_runs(self):
        x = lowrank((10, 9, 8), (2, 3, 2), noise=0.05)
        res = hooi(x, (2, 3, 2), n_iters=1, methods="auto")
        assert float(res.tucker.rel_error(x)) < 0.12
        assert len(res.trace) == 3 + 3     # init sweep + 1 HOOI sweep


class TestImplAndTrace:
    """impl= must reach the solvers, and traces must carry real wall-clock."""

    @pytest.mark.parametrize("fn,kw", [
        (thosvd, {}),
        (hooi, {"n_iters": 1}),
    ])
    def test_explicit_impl_parity(self, fn, kw):
        x = lowrank((9, 10, 8), (3, 3, 3), noise=0.02)
        a = fn(x, (3, 3, 3), methods="eig", impl="matfree", **kw)
        b = fn(x, (3, 3, 3), methods="eig", impl="explicit", **kw)
        np.testing.assert_allclose(float(a.tucker.rel_error(x)),
                                   float(b.tucker.rel_error(x)), atol=1e-5)

    @pytest.mark.parametrize("fn,kw", [
        (thosvd, {}),
        (hooi, {"n_iters": 1}),
    ])
    def test_trace_records_real_seconds(self, fn, kw):
        x = lowrank((12, 10, 8), (3, 3, 2), noise=0.05)
        res = fn(x, (3, 3, 2), methods="eig", **kw)
        assert all(t.seconds >= 0.0 for t in res.trace)
        assert any(t.seconds > 0.0 for t in res.trace)

    def test_impl_rejects_unknown(self):
        x = lowrank((6, 6, 6), (2, 2, 2))
        with pytest.raises(ValueError):
            thosvd(x, (2, 2, 2), methods="eig", impl="bogus")
