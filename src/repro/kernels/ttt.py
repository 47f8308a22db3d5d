"""Matricization-free TTT / Gram Pallas kernels (a-Tucker Sec. V).

Compute  z[i, r] = Σ_{a,b}  x[a, i, b] · y[a, r, b]  — the mode-(I,J)
tensor-times-tensor product contracting every mode except the target one.
Gram (S = Y_(n) Y_(n)^T) is the special case y ≡ x, exactly as the paper
treats it.

Two layouts, so the contracted axes are never padded or copied:

  ``ttt_nt``  the target mode is NOT last: views (I, B) or
      (A, I, B), B (the contiguous inner axes) on lanes.  Grid = (I/bi,
      R/br, [A/ba,] B/bb) with the reductions innermost, so the (bi, br)
      output tile stays resident in VMEM while the tensors stream through
      in their native layout.
  ``ttt_tn``  the target mode IS last: view (A, I), A (the
      merged outer axes) on sublanes.  Contracts over A as z = Xᵀ Y —
      tensor_ops' ``mode == N-1`` GEMM — instead of an (A, I, 1) view whose
      unit axis would land on the lanes and be padded 128-fold.

Edge blocks of I and R are ragged (never stored); ragged contraction edges
are zeroed in-kernel.  fp32 accumulation on the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .matmul import COMPILER_PARAMS, keep_first, precision_for


def _dot(x, y, contract: int):
    return jax.lax.dot_general(
        x, y, dimension_numbers=(((contract,), (contract,)), ((), ())),
        precision=precision_for(x.dtype),
        preferred_element_type=jnp.float32)


def _nt_kernel(x_ref, y_ref, o_ref, *, ba: int | None, b_total: int,
               bb: int):
    kb = pl.program_id(2 if ba is None else 3)   # the B sweep, innermost
    first = kb == 0
    if ba is not None:
        first = first & (pl.program_id(2) == 0)

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def contract(x, y):
        if b_total % bb:
            x = keep_first(x, 1, b_total - kb * bb)
            y = keep_first(y, 1, b_total - kb * bb)
        return _dot(x, y, 1)

    if ba is None:
        o_ref[...] += contract(x_ref[...], y_ref[...])
        return

    def one(a, carry):
        o_ref[...] += contract(x_ref[a], y_ref[a])
        return carry

    jax.lax.fori_loop(0, ba, one, 0)


@functools.partial(jax.jit,
                   static_argnames=("bi", "br", "bb", "ba", "interpret"))
def ttt_nt(x: jax.Array, y: jax.Array, *, bi: int, br: int, bb: int,
           ba: int = 1, interpret: bool = False) -> jax.Array:
    """z (I, R) = einsum('[a]ib,[a]rb->ir', x, y) on (I, B)/(R, B) or
    (A, I, B)/(A, R, B) views.  ``ba`` (3-D only) must divide A."""
    assert x.ndim == y.ndim and x.shape[:-2] == y.shape[:-2] and \
        x.shape[-1] == y.shape[-1], (x.shape, y.shape)
    i, b = x.shape[-2:]
    r = y.shape[-2]
    if x.ndim == 2:
        ba = None
        grid = (pl.cdiv(i, bi), pl.cdiv(r, br), pl.cdiv(b, bb))
        xs = pl.BlockSpec((bi, bb), lambda ii, rr, kb: (ii, kb))
        ys = pl.BlockSpec((br, bb), lambda ii, rr, kb: (rr, kb))
        os = pl.BlockSpec((bi, br), lambda ii, rr, kb: (ii, rr))
    else:
        a = x.shape[0]
        assert a % ba == 0, (x.shape, ba)
        grid = (pl.cdiv(i, bi), pl.cdiv(r, br), a // ba, pl.cdiv(b, bb))
        xs = pl.BlockSpec((ba, bi, bb), lambda ii, rr, aa, kb: (aa, ii, kb))
        ys = pl.BlockSpec((ba, br, bb), lambda ii, rr, aa, kb: (aa, rr, kb))
        os = pl.BlockSpec((bi, br), lambda ii, rr, aa, kb: (ii, rr))
    return pl.pallas_call(
        functools.partial(_nt_kernel, ba=ba, b_total=b, bb=bb),
        name="ttt_nt",
        grid=grid,
        in_specs=[xs, ys],
        out_specs=os,
        out_shape=jax.ShapeDtypeStruct((i, r), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(x, y)


def _tn_kernel(x_ref, y_ref, o_ref, *, a_total: int, ba: int):
    ka = pl.program_id(2)

    @pl.when(ka == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x, y = x_ref[...], y_ref[...]
    if a_total % ba:
        x = keep_first(x, 0, a_total - ka * ba)
        y = keep_first(y, 0, a_total - ka * ba)
    # (ba, bi)ᵀ @ (ba, br) -> (bi, br)
    o_ref[...] += _dot(x, y, 0)


@functools.partial(jax.jit, static_argnames=("bi", "br", "ba", "interpret"))
def ttt_tn(x: jax.Array, y: jax.Array, *, bi: int, br: int, ba: int,
           interpret: bool = False) -> jax.Array:
    """z (I, R) = xᵀ y for (A, I) and (A, R) views, contracting A."""
    a, i = x.shape
    a2, r = y.shape
    assert a == a2, (x.shape, y.shape)
    return pl.pallas_call(
        functools.partial(_tn_kernel, a_total=a, ba=ba),
        name="ttt_tn",
        grid=(pl.cdiv(i, bi), pl.cdiv(r, br), pl.cdiv(a, ba)),
        in_specs=[pl.BlockSpec((ba, bi), lambda ii, rr, ka: (ka, ii)),
                  pl.BlockSpec((ba, br), lambda ii, rr, ka: (ka, rr))],
        out_specs=pl.BlockSpec((bi, br), lambda ii, rr, ka: (ii, rr)),
        out_shape=jax.ShapeDtypeStruct((i, r), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(x, y)
