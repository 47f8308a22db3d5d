"""Tiled MXU matmul Pallas kernel: C (M,N) = A (M,K) @ B (K,N), fp32 accum.

Used for the first-mode / last-mode TTM cases of the matricization-free
st-HOSVD (paper Fig. 4: the boundary modes collapse to a single GEMM).

Blocking: (bm, bk) × (bk, bn) tiles streamed HBM→VMEM; grid =
(M/bm, N/bn, K/bk) with the contraction as the innermost (minor) grid dim so
the output tile stays resident in VMEM across the K sweep (revolving
accumulator pattern).  Dims need not divide their blocks: the edge blocks
of M and N only produce output rows/columns that are never stored, and a
ragged K edge is zeroed in-kernel (:func:`keep_first`) before it reaches
the MXU, so callers never pad (copy) the operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: scoped VMEM for every a-Tucker kernel: ops.py sizes blocks for about a
#: quarter of this, leaving room for double buffering and the in-kernel
#: masked and transposed copies (v5e has 128 MiB of VMEM per core)
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=48 << 20)


def keep_first(x: jax.Array, axis: int, n: int) -> jax.Array:
    """Zero every entry of ``x`` at index ≥ ``n`` along ``axis`` — the
    out-of-bounds part of an edge block, whose contents are undefined."""
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(idx < n, x, jnp.zeros_like(x))


def precision_for(dtype) -> jax.lax.Precision:
    """fp32 operands contract at full fp32 precision, like the ``matfree``
    backend; narrower dtypes take the MXU's native single pass."""
    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 \
        else jax.lax.Precision.DEFAULT


def _matmul_kernel(a_ref, b_ref, o_ref, *, k_total: int, bk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a, b = a_ref[...], b_ref[...]
    if k_total % bk:
        a = keep_first(a, 1, k_total - k * bk)
        b = keep_first(b, 0, k_total - k * bk)
    o_ref[...] += jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision_for(a.dtype),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
           bk: int = 128, interpret: bool = False) -> jax.Array:
    """Pallas tiled matmul.  Each block extent is the whole dim or a
    TPU-legal tile (multiple of 8 on sublanes, 128 on lanes)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_total=k, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(a, b)
