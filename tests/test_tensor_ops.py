"""Property tests for the matricization-free tensor ops (paper Sec. V)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import tensor_ops as T

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


def rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), dtype)


shapes3 = st.tuples(st.integers(2, 9), st.integers(2, 9), st.integers(2, 9))
shapes4 = st.tuples(st.integers(2, 6), st.integers(2, 6), st.integers(2, 6),
                    st.integers(2, 6))


class TestTTM:
    @given(shape=shapes3, mode=st.integers(0, 2), r=st.integers(1, 7),
           seed=st.integers(0, 10))
    def test_matfree_equals_explicit(self, shape, mode, r, seed):
        x = rand(shape, seed)
        u = rand((r, shape[mode]), seed + 1)
        np.testing.assert_allclose(
            T.ttm(x, u, mode), T.ttm_explicit(x, u, mode), rtol=2e-4, atol=2e-4)

    @given(shape=shapes4, mode=st.integers(0, 3))
    def test_4th_order(self, shape, mode):
        x = rand(shape)
        u = rand((3, shape[mode]), 1)
        np.testing.assert_allclose(
            T.ttm(x, u, mode), T.ttm_explicit(x, u, mode), rtol=2e-4, atol=2e-4)

    @given(shape=shapes3, mode=st.integers(0, 2))
    def test_identity(self, shape, mode):
        x = rand(shape)
        eye = jnp.eye(shape[mode])
        np.testing.assert_allclose(T.ttm(x, eye, mode), x, rtol=1e-5, atol=1e-5)

    @given(shape=shapes3, seed=st.integers(0, 5))
    def test_distinct_modes_commute(self, shape, seed):
        x = rand(shape, seed)
        u0 = rand((3, shape[0]), seed + 1)
        u2 = rand((4, shape[2]), seed + 2)
        a = T.ttm(T.ttm(x, u0, 0), u2, 2)
        b = T.ttm(T.ttm(x, u2, 2), u0, 0)
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)

    def test_shape_validation(self):
        x = rand((3, 4, 5))
        with pytest.raises(ValueError):
            T.ttm(x, rand((2, 99)), 1)


class TestGramTTT:
    @given(shape=shapes3, mode=st.integers(0, 2), seed=st.integers(0, 10))
    def test_gram_equals_explicit(self, shape, mode, seed):
        x = rand(shape, seed)
        np.testing.assert_allclose(
            T.gram(x, mode), T.gram_explicit(x, mode), rtol=2e-4, atol=2e-4)

    @given(shape=shapes3, mode=st.integers(0, 2))
    def test_gram_spd(self, shape, mode):
        s = np.asarray(T.gram(rand(shape), mode))
        np.testing.assert_allclose(s, s.T, rtol=1e-5, atol=1e-6)
        assert np.linalg.eigvalsh(s).min() > -1e-4

    @given(shape=shapes3, mode=st.integers(0, 2), r=st.integers(1, 6))
    def test_ttt_equals_explicit(self, shape, mode, r):
        x = rand(shape, 0)
        yshape = shape[:mode] + (r,) + shape[mode + 1:]
        y = rand(yshape, 1)
        np.testing.assert_allclose(
            T.ttt(x, y, mode), T.ttt_explicit(x, y, mode), rtol=2e-4, atol=2e-4)

    def test_gram_is_ttt_self(self):
        x = rand((4, 5, 6))
        np.testing.assert_allclose(T.gram(x, 1), T.ttt(x, x, 1), rtol=1e-5)


#: axes that fill no (8, 128) tile, orders 3 and 4
NATIVE_SHAPES = [(5, 7, 9), (5, 7, 9, 11)]
NATIVE_CASES = [(shape, mode, op, dtype)
                for shape in NATIVE_SHAPES for mode in range(len(shape))
                for op in ("ttm", "ttt") for dtype in ("float32", "bfloat16")]


@pytest.mark.parametrize("shape, mode, op, dtype", NATIVE_CASES)
def test_native_axes_equal_explicit_unfold(shape, mode, op, dtype):
    """TTM and TTT contract over the tensor's own axes, with no reshape to
    a merged view, and equal the explicit unfold → GEMM → fold baseline."""
    dtype = jnp.dtype(dtype)
    x = rand(shape, 0, dtype)
    if op == "ttm":
        other = rand((4, shape[mode]), 1, dtype)
        fn, ref = T.ttm, T.ttm_explicit
    else:
        other = rand(shape[:mode] + (4,) + shape[mode + 1:], 1, dtype)
        fn, ref = T.ttt, T.ttt_explicit
    got = fn(x, other, mode)
    want = ref(x, other, mode)
    assert got.shape == want.shape
    assert "reshape" not in str(
        jax.make_jaxpr(lambda a, b: fn(a, b, mode))(x, other))
    # bf16 outputs round to 8 bits; the sums' order differs
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * scale)


class TestFoldReconstruct:
    @given(shape=shapes3, mode=st.integers(0, 2))
    def test_unfold_fold_roundtrip(self, shape, mode):
        x = rand(shape)
        np.testing.assert_array_equal(
            T.fold(T.unfold(x, mode), mode, shape), x)

    def test_fro_norm_mode_invariant(self):
        x = rand((4, 5, 6))
        n = float(T.fro_norm(x))
        for mode in range(3):
            assert abs(float(jnp.linalg.norm(T.unfold(x, mode))) - n) < 1e-4

    def test_reconstruct_orthonormal_exact(self):
        rng = np.random.default_rng(0)
        core = rand((3, 4, 2), 5)
        factors = [jnp.asarray(np.linalg.qr(rng.standard_normal((d, r)))[0],
                               jnp.float32)
                   for d, r in zip((8, 9, 7), (3, 4, 2))]
        x = T.reconstruct(core, factors)
        # project back: core == X ×_n U^T
        back = x
        for m, u in enumerate(factors):
            back = T.ttm(back, u.T, m)
        np.testing.assert_allclose(back, core, rtol=1e-4, atol=1e-5)
