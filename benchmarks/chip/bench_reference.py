"""Plain st-HOSVD reference and the comparisons that decide ``correct``.

Imports nothing of the program.  The reference is the textbook sequentially
truncated HOSVD in float32 ``jax.numpy``: for each mode in natural order,
the leading eigenvectors of the mode's Gram matrix, then the tensor is
shrunk by them.  Where a mode is longer than the rest of the tensor, the
smaller Gram ``Y_(n)^T Y_(n)`` is diagonalised instead and the factor is the
orthonormal basis of ``Y_(n) V``: the same subspace, without an
``I_n x I_n`` eigensolve.

Every contraction goes through :func:`pdot`, at one of two precisions:

``highest``  float32 at ``Precision.HIGHEST``: what the configurations
             state, and what the reference runs at.
``bf16``     one bfloat16 pass (inputs rounded to bfloat16, products
             accumulated in float32), the same on every backend: what the
             TPU computes for a float32 product at ``Precision.DEFAULT``.
             This is the control: the reference put in the program's place
             at the step that a comparison can see.

The rank-adaptive reference follows the policy ``TuckerConfig(error_target=
eps)`` documents: each mode's share of the budget is ``eps^2 ||X||^2 / N``,
with ``||X||^2`` taken before any truncation, and the rank is the smallest
whose discarded energy (the current tensor's energy minus the top-r
eigenvalues of its mode Gram) fits that share.  The bound it reports is
the square root of the discarded energies' sum over ``||X||^2``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "bf16")


def _bf16(a):
    """``a`` rounded to bfloat16, held in float32 (``reduce_precision``
    survives XLA's excess-precision rewrites; a cast pair need not)."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def pdot(a, b, axes, precision: str):
    """``jnp.tensordot(a, b, axes)`` in float32 at ``precision``.

    ``bf16`` multiplies bfloat16 values held in float32 at the default
    precision: on a TPU one exact bfloat16 pass, on a CPU an exact float32
    product, so both backends accumulate the same products in float32."""
    if precision == "highest":
        return jnp.tensordot(a, b, axes, precision=jax.lax.Precision.HIGHEST)
    if precision != "bf16":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return jnp.tensordot(_bf16(a), _bf16(b), axes,
                         precision=jax.lax.Precision.DEFAULT)


@partial(jax.jit, static_argnums=(1, 2))
def _gram(y, mode: int, precision: str):
    others = [a for a in range(y.ndim) if a != mode]
    return pdot(y, y, (others, others), precision)


@partial(jax.jit, static_argnums=(2, 3))
def _ttm_t(y, u, mode: int, precision: str):
    """``y x_mode u^T``: mode ``mode`` shrinks from ``u.shape[0]`` to
    ``u.shape[1]``."""
    return jnp.moveaxis(pdot(u, y, ((0,), (mode,)), precision), 0, mode)


@partial(jax.jit, static_argnums=(1, 2))
def _cogram(y, mode: int, precision: str):
    """(Y_(n)^T Y_(n), Y_(n)) for a mode longer than the rest."""
    m = jnp.moveaxis(y, mode, 0).reshape(y.shape[mode], -1)
    return pdot(m, m, ((0,), (0,)), precision), m


@partial(jax.jit, static_argnums=(2, 3))
def _cofactor(m, v, rank: int, precision: str):
    z = pdot(m, v[:, ::-1][:, :rank], ((1,), (0,)), precision)
    return jnp.linalg.qr(z)[0]


def _mode_spectrum(y, mode: int, precision: str):
    """Descending eigenvalues of the mode Gram and a function giving the
    leading-r factor."""
    i_n = y.shape[mode]
    if i_n <= y.size // i_n:
        w, v = jnp.linalg.eigh(_gram(y, mode, precision))
        return w[::-1], lambda r: v[:, ::-1][:, :r]
    g, m = _cogram(y, mode, precision)
    w, v = jnp.linalg.eigh(g)
    return w[::-1], lambda r: _cofactor(m, v, r, precision)


def sthosvd(x, ranks, precision: str = "highest"):
    """Fixed-rank st-HOSVD: ``(core, factors)``."""
    y, factors = x, []
    for mode, r in enumerate(ranks):
        _, factor = _mode_spectrum(y, mode, precision)
        u = factor(int(r))
        factors.append(u)
        y = _ttm_t(y, u, mode, precision)
    return y, factors


def sthosvd_adaptive(x, error_target: float, precision: str = "highest"):
    """Rank-adaptive st-HOSVD under the documented equi-partitioned budget:
    ``(core, factors, ranks, error_bound)``."""
    n = x.ndim
    total = float(jnp.sum(x * x))
    budget = error_target ** 2 / n * total
    y, factors, ranks, discarded = x, [], [], 0.0
    for mode in range(n):
        evals, factor = _mode_spectrum(y, mode, precision)
        energy = float(jnp.sum(y * y))
        captured = np.cumsum(np.maximum(np.asarray(evals, np.float64), 0.0))
        tails = np.maximum(energy - captured, 0.0)
        fits = np.nonzero(tails <= budget)[0]
        r = int(fits[0]) + 1 if fits.size else len(captured)
        discarded += float(tails[r - 1])
        u = factor(r)
        factors.append(u)
        ranks.append(r)
        y = _ttm_t(y, u, mode, precision)
    return y, factors, tuple(ranks), math.sqrt(discarded / total)


def decompose(x, request: dict, precision: str = "highest"):
    """The reference answer for one request: ``(core, factors,
    error_bound)``, the bound None at fixed ranks.  ``request`` holds
    ``ranks`` or ``error_target``."""
    if request.get("error_target") is not None:
        core, factors, _, bound = sthosvd_adaptive(
            x, request["error_target"], precision)
        return core, factors, bound
    return (*sthosvd(x, request["ranks"], precision), None)


# -- comparisons -------------------------------------------------------------

def subspace_gap(u, u_ref) -> float:
    """``||U U^T - U_ref U_ref^T||_2`` in float64: the sine of the largest
    principal angle for orthonormal ``U``; a factor whose columns are not
    orthonormal reads large too.  Mismatched shapes read 1."""
    u = np.asarray(u, np.float64)
    u_ref = np.asarray(u_ref, np.float64)
    if u.shape != u_ref.shape:
        return 1.0
    w = np.concatenate([u, u_ref], axis=1)
    r = np.linalg.qr(w, mode="r")
    k = u.shape[1]
    d = np.concatenate([np.ones(k), -np.ones(k)])
    m = (r * d) @ r.T
    return float(np.max(np.abs(np.linalg.eigvalsh((m + m.T) / 2))))


@jax.jit
def _sq_norm(x):
    return jnp.sum(x * x)


def core_residual(x, core, factors) -> float:
    """``||core - X x_n U_n^T||_F / ||X||_F`` at ``highest``: whether the
    core is the projection of the input onto the factors.  Shapes that do
    not fit read 1."""
    if len(factors) != x.ndim or any(
            u.shape != (x.shape[m], core.shape[m])
            for m, u in enumerate(factors)):
        return 1.0
    y = x
    for mode, u in enumerate(factors):
        y = _ttm_t(y, u.astype(jnp.float32), mode, "highest")
    d = y - core.astype(jnp.float32)
    return float(jnp.sqrt(_sq_norm(d) / _sq_norm(x)))


def rel_error(x, core, factors) -> float:
    """``||X - core x_n U_n||_F / ||X||_F`` at ``highest``."""
    y = core.astype(jnp.float32)
    for mode, u in enumerate(factors):
        y = _ttm_t(y, u.astype(jnp.float32).T, mode, "highest")
    return float(jnp.sqrt(_sq_norm(x - y) / _sq_norm(x)))
