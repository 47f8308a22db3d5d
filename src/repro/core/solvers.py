"""Per-mode factor/core solvers for the flexible st-HOSVD (a-Tucker Sec. III).

Each solver consumes the current (partially shrunk) tensor ``y`` and a mode,
and returns ``(U, y_new)`` where ``U`` (I_n × R_n) has orthonormal columns
and ``y_new`` is the tensor with mode ``n`` shrunk to R_n:

  EIG  (paper Alg. 2 lines 6–8):  S = Y_(n)Y_(n)^T  → leading eigvecs → TTM.
  ALS  (paper Alg. 2 lines 10–13 + Alg. 3): rank-R_n alternating LS on
       Y_(n) ≈ L R^T, core = TTM(y, Qᵀ) for the final basis Q (the last
       R-update).  Departure from Alg. 3: L is orthonormalized (QR) every
       iteration, so the L-update is QR(TTT(y, R-tensor)) and the
       R-update TTM(y, Qᵀ), with no (LᵀL)⁻¹ or (RᵀR)⁻¹.  In exact
       arithmetic this is the same subspace sequence (span L_{k+1} =
       span Y_(n)Y_(n)ᵀL_k either way, i.e. orthogonal iteration); in
       float32 the normal-equation inverses of an L whose columns align
       with the leading direction lose the weak directions to rounding,
       a floor that more iterations do not lower.
  SVD  (paper Alg. 1; baseline only — always slowest, kept for Fig. 2).
  RAND (randomized range finder / sketched Gram, Minster–Saibaba–Kilmer
       [1905.07311]): Y_(n) Ω for a Gaussian test tensor Ω with
       ℓ = R_n + oversample columns → QR → optional power iterations →
       Rayleigh–Ritz rotation of the ℓ-dim sketch basis (an eig step on the
       ℓ×ℓ sketched Gram) truncated to R_n.  Cheap when R_n ≪ I_n: the
       I_n²·J_n Gram is replaced by O(I_n·ℓ·J_n) sketch contractions, all
       expressed through the same TTM/TTT/Gram backend primitives (no
       matricization).  Its singular-value tail is what rank-adaptive
       (``error_target``) plans read the per-mode rank off — see
       :func:`rand_sketch` and :meth:`repro.core.api.TuckerPlan.resolve_ranks`.

Everything is matricization-free (built on whichever registered
:mod:`repro.core.backend` supplies TTM/TTT/Gram); ``impl`` names an ops
backend — ``matfree`` (jnp contractions), ``explicit`` (unfold-based
baseline for the Fig. 8 comparison), ``pallas`` (hand-written TPU kernels),
or any custom-registered name.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import tensor_ops as T
from .backend import backend_ops, get_backend

DEFAULT_ALS_ITERS = 5  # paper Sec. III-B default


class SolveResult(NamedTuple):
    u: jax.Array       # (I_n, R_n) orthonormal factor
    y_new: jax.Array   # tensor with mode shrunk to R_n


def _scoped(name: str, f):
    """``f``, traced under the named scope ``name``."""
    def g(*args, **kw):
        with jax.named_scope(name):
            return f(*args, **kw)
    return g


def _scoped_ops(impl: str):
    """The backend's ``(ttm, gram, ttt)``, each traced under a named scope
    of its own name, so a compiled solve's profile names its contractions
    (the eigen and QR steps run under ``solve``)."""
    ttm, gram, ttt = backend_ops(impl)
    return _scoped("ttm", ttm), _scoped("gram", gram), _scoped("ttt", ttt)


# ---------------------------------------------------------------------------
# EIG solver
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mode", "rank", "impl"))
def eig_solve(y: jax.Array, mode: int, rank: int, *, impl: str = "matfree") -> SolveResult:
    ttm, gram, _ = _scoped_ops(impl)
    s = gram(y, mode)                                   # (I_n, I_n), fp32+ accum
    with jax.named_scope("solve"):
        _, vecs = jnp.linalg.eigh(
            s.astype(jnp.promote_types(s.dtype, jnp.float32)))
    u = vecs[:, -rank:][:, ::-1].astype(y.dtype)        # leading R_n eigvecs
    y_new = ttm(y, u.T, mode)                           # core update
    return SolveResult(u, y_new)


# ---------------------------------------------------------------------------
# ALS solver (Alg. 3)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mode", "rank", "num_iters", "impl"))
def als_solve(y: jax.Array, mode: int, rank: int, *,
              num_iters: int = DEFAULT_ALS_ITERS,
              seed: int = 0,
              impl: str = "matfree") -> SolveResult:
    if num_iters < 1:
        # the starting block is random: zero iterations would return a
        # random subspace as the factor
        raise ValueError(f"als_solve needs num_iters >= 1, got {num_iters}")
    ttm, _, ttt = _scoped_ops(impl)
    i_n = y.shape[mode]
    # sub-fp32 inputs (bf16/fp16) iterate in fp32 (the peak_bytes model in
    # plan.py assumes exactly this); fp32/fp64 keep their own precision
    cdtype = jnp.promote_types(y.dtype, jnp.float32)
    l0 = jax.random.normal(jax.random.PRNGKey(seed), (i_n, rank), dtype=cdtype)
    # the loop contracts y 2·num_iters + 1 times.  Matfree's ops contract
    # over y's own axes; a backend that takes merged views gets the
    # (before, mode, after) view, made once: on a tiled TPU layout that
    # reshape copies the whole input, and XLA repeats a copy made inside
    # the loop in every iteration.  The last mode's view is (before, mode),
    # the plain GEMM operand: a unit "after" axis would leave each row in a
    # tile of its own there
    yc, m = y.astype(cdtype), mode
    if not get_backend(impl).native_axes:
        a, n, b = T.split_dims(y.shape, mode)
        yc, m = yc.reshape((a, n) if b == 1 else (a, n, b)), 1

    def body(_, carry):
        _, r_t = carry
        # L ← Y_(n) R = TTT(y, R-tensor), then Q from a Householder QR of
        # L, which stays orthonormal on a rank-deficient L (a low-rank input)
        l_k = ttt(yc, r_t, m)
        with jax.named_scope("solve"):
            q = jnp.linalg.qr(l_k)[0]
        # R ← Y_(n)ᵀ Q: with QᵀQ = I the (LᵀL)⁻¹ of Alg. 3 is the identity;
        # after the last iteration this is the core, y projected onto Q
        return q, ttm(yc, q.T, m)

    # the unnormalized Gaussian start only scales R_0, not span(Y_(n) R_0)
    q, r_t = jax.lax.fori_loop(0, num_iters, body, (l0, ttm(yc, l0.T, m)))
    out_shape = y.shape[:mode] + (rank,) + y.shape[mode + 1:]
    return SolveResult(q.astype(y.dtype), r_t.reshape(out_shape).astype(y.dtype))


# ---------------------------------------------------------------------------
# SVD solver (original st-HOSVD; baseline)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mode", "rank", "impl"))
def svd_solve(y: jax.Array, mode: int, rank: int, *, impl: str = "matfree") -> SolveResult:
    """SVD mode solve (paper Alg. 1 line 3): thin SVD of the unfolding.

    The SVD solver *inherently* matricizes — the decomposition is defined on
    the explicit I_n×J_n unfolding, so no backend can supply a
    matricization-free version (this is why ``OpsBackend.matricizes`` is a
    backend property but SVD steps pay the unfold copy on every backend).
    ``impl`` is still validated against the registry so unknown backends are
    rejected here exactly as in the EIG/ALS solvers, instead of being
    silently accepted.
    """
    get_backend(impl)  # reject unknown backends; ops themselves unused
    y2 = T.unfold(y, mode)
    cdtype = jnp.promote_types(y.dtype, jnp.float32)
    with jax.named_scope("solve"):
        u, s, vt = jnp.linalg.svd(y2.astype(cdtype), full_matrices=False)
    u = u[:, :rank]
    core2 = s[:rank, None] * vt[:rank]                  # Σ V^T
    out_shape = y.shape[:mode] + (rank,) + y.shape[mode + 1:]
    return SolveResult(u.astype(y.dtype), T.fold(core2, mode, out_shape).astype(y.dtype))


# ---------------------------------------------------------------------------
# RAND solver (randomized range finder, Minster–Saibaba–Kilmer 1905.07311)
# ---------------------------------------------------------------------------

DEFAULT_OVERSAMPLE = 8   # ℓ = R_n + oversample sketch columns
DEFAULT_POWER_ITERS = 1  # subspace iterations sharpening the sketch basis


@partial(jax.jit, static_argnames=("mode", "width", "power_iters", "seed", "impl"))
def rand_sketch(y: jax.Array, mode: int, width: int, *,
                power_iters: int = DEFAULT_POWER_ITERS,
                seed: int = 0,
                impl: str = "matfree"):
    """One-shot mode sketch: everything a rank decision needs, in one pass.

    Draws a Gaussian test tensor Ω (mode ``mode`` sized ``width`` = ℓ),
    forms the range sample ``Y_(n) Ω_(n)^T`` via the backend TTT kernel
    (never materializing an unfolding), orthonormalizes it, optionally
    runs ``power_iters`` subspace iterations (TTM project → TTT expand →
    QR), and Rayleigh–Ritz diagonalizes the ℓ×ℓ sketched Gram.

    Returns ``(q, b, evals, vecs, energy)``:

    - ``q``      (I_n, ℓ)  orthonormal sketch basis,
    - ``b``      tensor with mode shrunk to ℓ: ``TTM(y, qᵀ, mode)``,
    - ``evals``  (ℓ,) ascending eigenvalues of ``Gram(b, mode)`` — the
      squared sketched singular values of the unfolding,
    - ``vecs``   (ℓ, ℓ) matching eigenvectors,
    - ``energy`` scalar ``||y||_F²``.

    The captured energy of a rank-r truncation of this basis is exactly
    ``sum(evals[-r:])``, so the *actual* discarded energy at rank r is
    ``energy - sum(evals[-r:])`` — an exact tail for the factor that will
    really be used, which is what makes the per-mode HOSVD error budget
    check in rank-adaptive execution a guarantee rather than an estimate.
    """
    with jax.named_scope(f"mode{mode}.rand"):
        return _sketch(y, mode, width, power_iters=power_iters, seed=seed,
                       impl=impl)


def _sketch(y, mode: int, width: int, *, power_iters: int, seed: int,
            impl: str):
    """The body of :func:`rand_sketch`, traced inline by :func:`rand_solve`
    (whose sweep step already carries the mode's scope)."""
    ttm, gram, ttt = _scoped_ops(impl)
    cdtype = jnp.promote_types(y.dtype, jnp.float32)
    yc = y.astype(cdtype)
    energy = jnp.sum(jnp.square(yc))
    w_shape = y.shape[:mode] + (width,) + y.shape[mode + 1:]
    w = jax.random.normal(jax.random.PRNGKey(seed), w_shape, dtype=cdtype)
    ym = ttt(yc, w, mode)                                # (I_n, ℓ) range sample
    with jax.named_scope("solve"):
        q, _ = jnp.linalg.qr(ym)
    for _ in range(power_iters):
        b = ttm(yc, q.T, mode)                           # project: mode → ℓ
        ym = ttt(yc, b, mode)                            # expand: Y_(n)Y_(n)ᵀ Q
        with jax.named_scope("solve"):
            q, _ = jnp.linalg.qr(ym)
    b = ttm(yc, q.T, mode)
    gb = gram(b, mode)                                   # (ℓ, ℓ) sketched Gram
    with jax.named_scope("solve"):
        evals, vecs = jnp.linalg.eigh(
            gb.astype(jnp.promote_types(gb.dtype, jnp.float32)))
    return q, b, evals, vecs, energy


@partial(jax.jit, static_argnames=("mode", "rank", "oversample", "power_iters",
                                   "seed", "impl"))
def rand_solve(y: jax.Array, mode: int, rank: int, *,
               oversample: int = DEFAULT_OVERSAMPLE,
               power_iters: int = DEFAULT_POWER_ITERS,
               seed: int = 0,
               impl: str = "matfree") -> SolveResult:
    """Randomized mode solve: sketch at width ℓ = rank + oversample, then the
    existing eig machinery refines within the sketch — the Rayleigh–Ritz
    rotation *is* an eig step on the ℓ×ℓ sketched Gram, truncated to R_n."""
    width = min(y.shape[mode], rank + oversample)
    q, b, _, vecs, _ = _sketch(
        y, mode, width, power_iters=power_iters, seed=seed, impl=impl)
    u, y_new = _ritz_rotate(q, b, vecs, mode, rank, impl)
    return SolveResult(u.astype(y.dtype), y_new.astype(y.dtype))


def _ritz_rotate(q, b, vecs, mode: int, rank: int, impl: str):
    """Top-``rank`` Rayleigh–Ritz rotation of a sketch: the factor
    ``u = q·v`` and ``b`` with mode ``mode`` rotated from ℓ to ``rank``
    (``v`` the leading Ritz vectors), both in ``q``'s dtype."""
    v = vecs[:, -rank:][:, ::-1].astype(q.dtype)         # leading R_n Ritz vecs
    ttm, _, _ = _scoped_ops(impl)
    u = jnp.dot(q, v, precision=jax.lax.Precision.HIGHEST)
    return u, ttm(b, v.T, mode)                          # rotate core: ℓ → R_n


@partial(jax.jit, static_argnames=("mode", "rank", "dtype", "impl"))
def ritz_shrink(q: jax.Array, b: jax.Array, vecs: jax.Array, mode: int,
                rank: int, *, dtype, impl: str = "matfree") -> SolveResult:
    """The finish of one sketched mode, as one program: the top-``rank``
    Ritz factor and the tensor shrunk to ``rank`` along ``mode``, both cast
    to ``dtype``, from a :func:`rand_sketch`'s ``(q, b, vecs)``.  Compiled
    once per mode, rank, sketch shape and backend, so the rank-adaptive
    pass dispatches it without waiting on the device."""
    with jax.named_scope(f"mode{mode}.rand"):
        u, y_new = _ritz_rotate(q, b, vecs, mode, rank, impl)
    return SolveResult(u.astype(dtype), y_new.astype(dtype))


@jax.jit
def sketch_readout(evals: jax.Array, energy: jax.Array) -> jax.Array:
    """A :func:`rand_sketch`'s ``evals`` followed by its ``energy``, in one
    ``(ℓ+1,)`` array: everything a rank decision reads, in one transfer."""
    dtype = jnp.promote_types(evals.dtype, energy.dtype)
    return jnp.concatenate([evals.astype(dtype), energy[None].astype(dtype)])


SOLVERS = {"eig": eig_solve, "als": als_solve, "svd": svd_solve, "rand": rand_solve}
EIG, ALS, SVD, RAND = "eig", "als", "svd", "rand"
