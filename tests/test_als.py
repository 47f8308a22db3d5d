"""The ALS solver against a plain float64 st-HOSVD, the selector's choice of
ALS at the paper's Boats and Cavity widths, and the ``als_passes`` the
``execute`` span reports.

The data model is the benchmark's: a Gaussian Tucker core at the ranks times
orthonormal factors, plus 5% Gaussian noise (relative to the low-rank
part's norm).  At 64×48×2000, ranks 10, the float32 program reads 2e-7 to
3e-7 against the float64 reference on both numbers; an ALS that iterates on
a non-orthonormal L through the normal-equation inverses floors at 1e-5 to
8e-5 on most seeds, however many iterations it runs, which these limits
refuse."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import TuckerConfig, als_solve, decompose, plan
from repro.core import tensor_ops as T
from repro.core.selector import default_selector

SHAPE = (64, 48, 2000)
RANKS = (10, 10, 10)
NOISE = 0.05
#: float32 subspace and core agreement with the float64 reference: room
#: above the float32 program's rounding for another float32 ordering of
#: the same sums, below the inverse-based iteration's floor
SUBSPACE_LIMIT = 1e-5
CORE_LIMIT = 1e-5

BOATS = ((320, 240, 7000), (10, 10, 10))
CAVITY = ((100, 100, 10000), (20, 20, 20))
#: Boats mode 1, (I, R, J) = (240, 10, 70,000): None leaves it unpinned,
#: since no chip timing of the two solvers at that step decides it yet
BOATS_MODE1 = None
#: the serve cell's buckets: Cavity's 100×100 frames padded to 104, ranks 20
SERVE_BUCKETS = [((104, 104, t), (20, 20, 20))
                 for t in (1000, 2000, 5000, 10000)]


def lowrank(seed, shape, ranks, noise):
    """Float32 Tucker tensor at ``ranks`` plus ``noise``-relative noise."""
    rng = np.random.default_rng(seed)
    core = rng.standard_normal(ranks)
    x = core
    for mode, (d, r) in enumerate(zip(shape, ranks)):
        u = np.linalg.qr(rng.standard_normal((d, r)))[0]
        x = np.moveaxis(np.tensordot(u, x, axes=(1, mode)), 0, mode)
    scale = noise * np.linalg.norm(core) / math.sqrt(x.size)
    return (x + scale * rng.standard_normal(shape)).astype(np.float32)


def _ttm_t(y, u, mode):
    """``y ×_mode uᵀ`` in float64."""
    return np.moveaxis(np.tensordot(u.T, y, axes=(1, mode)), 0, mode)


def sthosvd64(x, ranks):
    """Plain st-HOSVD in float64, modes in natural order: the leading
    eigenvectors of each mode's Gram, then the shrink by them."""
    y = np.asarray(x, np.float64)
    factors = []
    for mode, r in enumerate(ranks):
        m = np.moveaxis(y, mode, 0).reshape(y.shape[mode], -1)
        _, vecs = np.linalg.eigh(m @ m.T)
        u = vecs[:, ::-1][:, :r]
        factors.append(u)
        y = _ttm_t(y, u, mode)
    return y, factors


def subspace_gap(u, u_ref):
    """``||U Uᵀ − U_ref U_refᵀ||_2`` for orthonormal bases of one size: the
    sine of the largest principal angle, ``||(I − U_ref U_refᵀ) U||_2``."""
    u = np.asarray(u, np.float64)
    return float(np.linalg.norm(u - u_ref @ (u_ref.T @ u), 2))


def core_residual(x, core, factors):
    """``||core − X ×_n U_nᵀ|| / ||X||`` in float64: whether the core is the
    input projected onto the factors."""
    y = np.asarray(x, np.float64)
    for mode, u in enumerate(factors):
        y = _ttm_t(y, np.asarray(u, np.float64), mode)
    return float(np.linalg.norm(y - np.asarray(core, np.float64))
                 / np.linalg.norm(np.asarray(x, np.float64)))


@pytest.mark.parametrize("seed", [1, 3, 7, 9, 113])
def test_als_matches_float64_sthosvd(seed):
    x = lowrank(seed, SHAPE, RANKS, NOISE)
    res = decompose(x, TuckerConfig(ranks=RANKS, methods=("als",) * 3))
    _, ref = sthosvd64(x, RANKS)
    gaps = [subspace_gap(u, u_ref)
            for u, u_ref in zip(res.tucker.factors, ref)]
    assert max(gaps) <= SUBSPACE_LIMIT, gaps
    assert core_residual(x, res.tucker.core, res.tucker.factors) \
        <= CORE_LIMIT


@pytest.mark.parametrize("shape, ranks, methods", [
    (*BOATS, ("eig", BOATS_MODE1, "als")),
    (*CAVITY, ("eig", "eig", "als")),
    *[(*bucket, ("eig", "eig", "als")) for bucket in SERVE_BUCKETS],
])
def test_tpu_cost_model_selector_choice(shape, ranks, methods):
    # the TPU has no trained tree: its cost file decides, pricing
    # contractions at the MXU's 128-wide output.  Boats' mode 0 takes the
    # one-pass Gram over ALS's eleven; the long last modes (an I_n³ eigh)
    # stay on ALS, and Cavity's picks are the unpadded model's
    sel = default_selector(platform="tpu", backend="matfree")
    p = plan(shape, jnp.float32, TuckerConfig(ranks=ranks), selector=sel)
    pinned = [m for m, want in enumerate(methods) if want is not None]
    assert [p.methods[m] for m in pinned] == [methods[m] for m in pinned]


def test_tpu_cost_model_keeps_cavity_refine_choice():
    # cavity.target's refine sweep: the rank-adaptive plan's mode order at
    # the ranks the sketch chose (the published 20 at ε = 0.1)
    sel = default_selector(platform="tpu", backend="matfree")
    sketch = plan(CAVITY[0], jnp.float32, TuckerConfig(error_target=0.1),
                  selector=sel)
    order = tuple(s.mode for s in sketch.schedule)
    p = plan(CAVITY[0], jnp.float32,
             TuckerConfig(ranks=CAVITY[1], mode_order=order), selector=sel)
    assert p.methods == ("eig", "eig", "als")


def test_als_passes_hand_count_for_boats():
    # mode 0 reads the whole input, mode 1 a 1/32 of it (320 → 10), mode 2
    # a 1/768 (240 → 10 too); each ALS step reads its input 2·5 + 1 times
    p = plan(BOATS[0], jnp.float32,
             TuckerConfig(ranks=BOATS[1], methods=("als", "als", "als")))
    assert p.als_passes == pytest.approx(11 * (1 + 1 / 32 + 1 / 768))
    assert round(p.als_passes, 2) == 11.36


def test_execute_and_plan_spans_carry_methods_and_als_passes():
    x = lowrank(0, (12, 10, 40), (3, 3, 3), NOISE)
    cfg = TuckerConfig(ranks=(3, 3, 3), methods=("eig", "als", "als"))
    with obs.capture() as buf:
        p = plan(x.shape, x.dtype, cfg)
        p.execute(x)
    spans = {e["name"]: e for e in obs.iter_spans(buf.events())}
    assert spans["plan"]["methods"] == ["eig", "als", "als"]
    # mode 1 reads 3·10·40 of 12·10·40 elements, mode 2 3·3·40
    want = 11 * (3 * 10 * 40 + 3 * 3 * 40) / (12 * 10 * 40)
    assert spans["execute"]["als_passes"] == pytest.approx(want)
    assert p.als_passes == pytest.approx(want)


def _input_reshapes(fn, y):
    """``(inside a loop, new shape)`` for each ``reshape`` of an array of
    ``y``'s size anywhere in ``fn``'s jaxpr: the views of the whole input."""
    found = []

    def walk(jaxpr, in_loop):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "reshape" and \
                    math.prod(eqn.invars[0].aval.shape) == y.size:
                found.append((in_loop, tuple(eqn.params["new_sizes"])))
            loop = in_loop or eqn.primitive.name in ("scan", "while")
            for v in eqn.params.values():
                for sub in v if isinstance(v, tuple) else (v,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner, loop)

    walk(jax.make_jaxpr(fn)(y).jaxpr, False)
    return found


#: axes that fill no (8, 128) tile
VIEW_SHAPE = (6, 7, 45)


@pytest.mark.parametrize("impl", ["matfree", "explicit", "pallas"])
@pytest.mark.parametrize("mode", [0, 1, 2])
def test_als_solve_factor_orthonormal_and_core_is_projection(impl, mode):
    x = lowrank(2, VIEW_SHAPE, (3, 3, 3), NOISE)
    u, core = als_solve(jnp.asarray(x), mode, 3, impl=impl)
    u64 = np.asarray(u, np.float64)
    np.testing.assert_allclose(u64.T @ u64, np.eye(3), atol=1e-5)
    want = _ttm_t(np.asarray(x, np.float64), u64, mode)
    assert np.linalg.norm(np.asarray(core, np.float64) - want) \
        <= 1e-5 * np.linalg.norm(np.asarray(x, np.float64))


@pytest.mark.parametrize("impl, mode", [
    ("matfree", 0), ("matfree", 1), ("matfree", 2),
    ("pallas", 0), ("pallas", 2), ("explicit", 0), ("explicit", 2),
])
def test_als_solve_makes_the_input_view_once_before_its_loop(impl, mode):
    """Matfree contracts over the input's own axes and reshapes it
    nowhere.  The Pallas kernels and the unfold baseline take the
    (before, mode, after) view ((before, mode) for the last mode), made
    once, before the loop; the kernels reshape the input nowhere else,
    while the baseline unfolds in every contraction.  (Mode 1's view is
    the input itself.)"""
    y = jnp.zeros(VIEW_SHAPE, jnp.float32)
    found = _input_reshapes(lambda y: als_solve(y, mode, 3, impl=impl), y)
    if impl == "matfree":
        assert found == []
        return
    a, n, b = T.split_dims(VIEW_SHAPE, mode)
    view = (a, n) if b == 1 else (a, n, b)
    assert [in_loop for in_loop, shape in found if shape == view] \
        == [False], found
    if impl == "pallas":
        assert len(found) == 1, found
