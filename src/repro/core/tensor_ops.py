"""Matricization-free dense tensor operations (a-Tucker, Sec. V).

The paper's insight: TTM / TTT / Gram on mode ``n`` never need an explicit
unfold.  Each is one contraction over the tensor's own axes: TTM contracts
``U``'s columns with axis ``n`` and leaves every other axis where it is;
TTT and Gram contract every axis but ``n``.  One ``lax.dot_general`` each,
with the original axes as its contracting or free dimensions, so XLA
chooses the layouts: a relayout, where one is needed, fuses into the dot's
operand read, or is made once for a loop that contracts one tensor often.

No op reshapes to a merged ``(A, I_n, B)`` or ``(I_n, J_n)`` view.  Such a
reshape is free in row-major memory, but not on a TPU, whose arrays are
tiled in ``(8, 128)`` blocks over the two minor axes: a merge across the
minor axis moves every element, a copy of the whole tensor.

``*_explicit`` variants materialize the mode-n unfolding first (moveaxis →
copy → GEMM → fold) and exist as the paper's explicit-matricization baseline
(Fig. 8 benchmark).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Shape helpers
# ---------------------------------------------------------------------------

def split_dims(shape: tuple[int, ...], mode: int) -> tuple[int, int, int]:
    """Return (A, I_n, B): dims merged before / along / after ``mode``."""
    a = math.prod(shape[:mode]) if mode > 0 else 1
    b = math.prod(shape[mode + 1:]) if mode < len(shape) - 1 else 1
    return a, shape[mode], b


# ---------------------------------------------------------------------------
# Matricization-free ops
# ---------------------------------------------------------------------------

def ttm(x: jax.Array, u: jax.Array, mode: int, *,
        precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """Mode-``mode`` tensor-times-matrix:  Y = X ×_mode U,  U: (R, I_mode).

    Matricization-free: one contraction of ``u``'s columns with axis
    ``mode`` of ``x``.  Its result leads with R, which then moves to
    ``mode`` (nothing moves for mode 0); for the last mode ``x`` leads, so
    R comes out last, where it belongs.
    """
    if u.ndim != 2 or u.shape[1] != x.shape[mode]:
        raise ValueError(f"ttm: U {u.shape} incompatible with mode {mode} of {x.shape}")
    if mode == x.ndim - 1:
        return jax.lax.dot_general(x, u, (((mode,), (1,)), ((), ())),
                                   precision=precision)
    y = jax.lax.dot_general(u, x, (((1,), (mode,)), ((), ())),
                            precision=precision)
    return jnp.moveaxis(y, 0, mode)


def ttm_chain(x: jax.Array, us: dict[int, jax.Array] | list, *,
              precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """Apply TTMs on several distinct modes (order-independent result)."""
    items = us.items() if isinstance(us, dict) else enumerate(us)
    y = x
    for mode, u in items:
        if u is not None:
            y = ttm(y, u, mode, precision=precision)
    return y


def gram(x: jax.Array, mode: int, *,
         precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """S = Y_(n) Y_(n)^T  (I_n × I_n) without forming Y_(n): TTT with y ≡ x
    (paper Sec. V)."""
    return ttt(x, x, mode, precision=precision)


def ttt(x: jax.Array, y: jax.Array, mode: int, *,
        precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """Mode-(I,J) product contracting every mode except ``mode``.

    x: (I_1..I_n..I_N), y: (I_1..R_n..I_N) with all non-``mode`` dims equal.
    Returns Z (I_n × R_n):  z[i,r] = Σ_other x[..i..] y[..r..].
    """
    if x.ndim != y.ndim:
        raise ValueError("ttt: rank mismatch")
    for m in range(x.ndim):
        if m != mode and x.shape[m] != y.shape[m]:
            raise ValueError(f"ttt: common mode {m} differs: {x.shape} vs {y.shape}")
    other = tuple(m for m in range(x.ndim) if m != mode)
    return jax.lax.dot_general(
        x, y,
        dimension_numbers=((other, other), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32 if x.dtype != jnp.float64 else None,
    ).astype(jnp.promote_types(x.dtype, jnp.float32))


# ---------------------------------------------------------------------------
# Explicit-matricization baseline (paper Fig. 3 workflow; used by Fig. 8)
# ---------------------------------------------------------------------------

def unfold(x: jax.Array, mode: int) -> jax.Array:
    """Mode-n matricization Y_(n) (I_n × J_n).  Materializes a copy."""
    return jnp.moveaxis(x, mode, 0).reshape(x.shape[mode], -1)


def fold(mat: jax.Array, mode: int, shape: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`unfold` for a tensor of target ``shape``."""
    full = (shape[mode],) + shape[:mode] + shape[mode + 1:]
    return jnp.moveaxis(mat.reshape(full), 0, mode)


def ttm_explicit(x: jax.Array, u: jax.Array, mode: int, *,
                 precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """TTM via explicit matricization: unfold → GEMM → fold."""
    y2 = jnp.dot(u, unfold(x, mode), precision=precision)
    out_shape = x.shape[:mode] + (u.shape[0],) + x.shape[mode + 1:]
    return fold(y2, mode, out_shape)


def gram_explicit(x: jax.Array, mode: int, *,
                  precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    y2 = unfold(x, mode)
    return jnp.dot(y2, y2.T, precision=precision)


def ttt_explicit(x: jax.Array, y: jax.Array, mode: int, *,
                 precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    return jnp.dot(unfold(x, mode), unfold(y, mode).T, precision=precision)


# ---------------------------------------------------------------------------
# Norms / reconstruction
# ---------------------------------------------------------------------------

def fro_norm(x: jax.Array) -> jax.Array:
    xf = x.reshape(-1)
    return jnp.sqrt(jnp.dot(xf, xf, precision=jax.lax.Precision.HIGHEST))


def reconstruct(core: jax.Array, factors: list[jax.Array]) -> jax.Array:
    """X̂ = G ×_1 U^(1) ··· ×_N U^(N).  factors[n]: (I_n, R_n)."""
    y = core
    for mode, u in enumerate(factors):
        y = ttm(y, u, mode)  # u is (I_n, R_n): contracts R_n, expands to I_n
    return y


def rel_error(x: jax.Array, core: jax.Array, factors: list[jax.Array]) -> jax.Array:
    """‖X − X̂‖_F / ‖X‖_F (paper Table III metric)."""
    return fro_norm(x - reconstruct(core, factors)) / fro_norm(x)
