"""Per-kernel allclose sweeps vs the ref.py oracle (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import matmul, ops, ref

RNG = np.random.default_rng(0)


def arr(shape, dtype=jnp.float32, seed=None):
    r = np.random.default_rng(seed) if seed is not None else RNG
    return jnp.asarray(r.standard_normal(shape), dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=4e-2, atol=4e-2)}


class TestMatmulKernel:
    @pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 384),
                                       (128, 256, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_aligned(self, m, k, n, dtype):
        a, b = arr((m, k), dtype), arr((k, n), dtype)
        got = matmul(a, b, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.matmul_ref(a, b)), **TOL[dtype])

    def test_block_shapes(self):
        a, b = arr((256, 256)), arr((256, 256))
        want = ref.matmul_ref(a, b)
        for bm, bn, bk in [(128, 128, 128), (64, 128, 256), (8, 128, 64)]:
            got = matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4)


class TestTTMOp:
    # sweep: non-divisible shapes exercise the padding path; every mode
    # position exercises a different kernel (first/last = GEMM, interior =
    # batched) — the paper's Fig. 4 structure
    @pytest.mark.parametrize("shape,mode,r", [
        ((5, 37, 19), 1, 7), ((33, 12, 50), 0, 9), ((13, 21, 40), 2, 5),
        ((4, 9, 11, 6), 2, 3), ((130, 140, 3), 0, 64), ((3, 200, 129), 1, 130),
        ((260, 7, 5), 0, 11), ((2, 3, 4, 5, 6), 2, 2),
    ])
    def test_vs_oracle(self, shape, mode, r):
        x = arr(shape, seed=1)
        u = arr((r, shape[mode]), seed=2)
        got = ops.ttm(x, u, mode)
        want = ref.ttm_full_ref(x, u, mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = arr((8, 40, 24), dtype, seed=3)
        u = arr((6, 40), dtype, seed=4)
        got = ops.ttm(x, u, 1)
        want = ref.ttm_full_ref(x, u, 1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL[dtype])


class TestGramTTTOps:
    @pytest.mark.parametrize("shape,mode", [
        ((5, 37, 19), 1), ((33, 12, 50), 0), ((13, 21, 40), 2),
        ((4, 9, 11, 6), 3), ((129, 6, 7), 0),
    ])
    def test_gram_vs_oracle(self, shape, mode):
        x = arr(shape, seed=5)
        got = ops.gram(x, mode)
        want = ref.gram_full_ref(x, mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("shape,mode,r", [
        ((5, 37, 19), 1, 7), ((13, 21, 40), 2, 5), ((9, 8, 7), 0, 3),
    ])
    def test_ttt_vs_oracle(self, shape, mode, r):
        x = arr(shape, seed=6)
        yshape = shape[:mode] + (r,) + shape[mode + 1:]
        y = arr(yshape, seed=7)
        got = ops.ttt(x, y, mode)
        a = int(np.prod(shape[:mode])) if mode else 1
        x3 = x.reshape(a, shape[mode], -1)
        y3 = y.reshape(a, r, -1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref.ttt_ref(x3, y3)),
                                   rtol=3e-4, atol=3e-4)


class TestRaggedTiles:
    """Tiles shrunk so small shapes hit what only large ones hit at the
    real tile sizes: several output tiles, ragged edge blocks on every kind
    of axis (masked in-kernel, never padded), and the transposed view of a
    tensor whose last axis is narrower than a lane tile."""

    @pytest.fixture(autouse=True)
    def small_tiles(self, monkeypatch):
        monkeypatch.setattr(ops, "_TILE", 128)
        monkeypatch.setattr(ops, "_BLOCK_BYTES", 16 << 10)

    @pytest.mark.parametrize("shape,mode,r", [
        ((300, 7, 150), 0, 9), ((7, 300, 150), 1, 130), ((9, 150, 300), 2, 5),
        ((150, 300, 6), 2, 4), ((300, 150, 5), 0, 3), ((6, 140, 5, 3), 1, 7),
    ])
    def test_ops_vs_oracle(self, shape, mode, r):
        x = arr(shape, seed=11)
        u = arr((r, shape[mode]), seed=12)
        y = arr(shape[:mode] + (r,) + shape[mode + 1:], seed=13)
        np.testing.assert_allclose(np.asarray(ops.ttm(x, u, mode)),
                                   np.asarray(ref.ttm_full_ref(x, u, mode)),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(ops.gram(x, mode)),
                                   np.asarray(ref.gram_full_ref(x, mode)),
                                   rtol=3e-4, atol=3e-4)
        a = int(np.prod(shape[:mode]))
        want = ref.ttt_ref(x.reshape(a, shape[mode], -1), y.reshape(a, r, -1))
        np.testing.assert_allclose(np.asarray(ops.ttt(x, y, mode)),
                                   np.asarray(want), rtol=3e-4, atol=3e-4)


class TestKernelInSolver:
    def test_sthosvd_with_pallas_gram(self):
        """The kernel path plugs into the real algorithm: one EIG mode solve
        computed with the Pallas Gram matches the jnp path."""
        from repro.core import tensor_ops as T
        x = arr((24, 30, 16), seed=8)
        s_pallas = ops.gram(x, 1)
        s_jnp = T.gram(x, 1)
        np.testing.assert_allclose(np.asarray(s_pallas), np.asarray(s_jnp),
                                   rtol=3e-4, atol=3e-4)
        wp = np.linalg.eigh(np.asarray(s_pallas))[1][:, -4:]
        wj = np.linalg.eigh(np.asarray(s_jnp))[1][:, -4:]
        np.testing.assert_allclose(wp @ wp.T, wj @ wj.T, atol=1e-3)


class TestS6ScanKernel:
    """Fused S6 selective-scan kernel vs the chunked-jnp oracle."""

    @pytest.mark.parametrize("shape,bd,bt", [
        ((2, 128, 64, 8), 32, 16),
        ((1, 64, 32, 4), 32, 64),
        ((3, 96, 16, 16), 16, 32),
    ])
    def test_vs_oracle(self, shape, bd, bt):
        from repro.kernels.s6_scan import s6_scan_fwd
        from repro.models.ssm import _s6_scan
        B, T, Di, N = shape
        r = np.random.default_rng(0)
        x = jnp.asarray(r.standard_normal((B, T, Di)), jnp.float32)
        dt = jnp.abs(jnp.asarray(r.standard_normal((B, T, Di)), jnp.float32)) * 0.1
        bm = jnp.asarray(r.standard_normal((B, T, N)), jnp.float32)
        cm = jnp.asarray(r.standard_normal((B, T, N)), jnp.float32)
        a = -jnp.abs(jnp.asarray(r.standard_normal((Di, N)), jnp.float32))
        y_ref, _ = _s6_scan(x, dt, bm, cm, a, chunk=32)
        y = s6_scan_fwd(x, dt, bm, cm, a, bd=bd, bt=bt)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-4)
