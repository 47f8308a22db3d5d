"""Matricization-free interior-mode TTM Pallas kernel (a-Tucker Sec. V).

Computes  out[a, r, b] = Σ_i  u[r, i] · x[a, i, b]  on the (A, I_n, B) view
of the tensor — i.e. the paper's batched-GEMM organization of mode-n TTM,
with the BlockSpec index maps playing the role of the (outer, along, inner)
loop split: grid dim 0 walks the merged *outer* loops (A, ``ba`` slices
per step), dims 1/2 tile the output (R, B), and dim 3 is the contraction
sweep along mode n.

The tensor is NEVER unfolded: the x BlockSpec reads (ba, bi, bb) tiles
straight from the tensor's native row-major layout (B is the contiguous
axis → lane dimension; I_n is the sublane dimension), so HBM traffic equals
the tensor's footprint with zero transpose/copy — the TPU analogue of the
paper's in-place batched GEMM on CPU/GPU.  Edge blocks of R and B are
ragged (never stored); a ragged I edge is zeroed in-kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .matmul import COMPILER_PARAMS, keep_first, precision_for


def _ttm_kernel(u_ref, x_ref, o_ref, *, ba: int, i_total: int, bi: int):
    ii = pl.program_id(3)

    @pl.when(ii == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    u = u_ref[...]
    if i_total % bi:
        u = keep_first(u, 1, i_total - ii * bi)

    def one(a, carry):
        x = x_ref[a]
        if i_total % bi:
            x = keep_first(x, 0, i_total - ii * bi)
        # (br, bi) @ (bi, bb) -> (br, bb), accumulated in fp32 on the MXU
        o_ref[a] += jax.lax.dot_general(
            u, x, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=precision_for(x.dtype),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, ba, one, 0)


@functools.partial(jax.jit,
                   static_argnames=("br", "bb", "bi", "ba", "interpret"))
def ttm_interior(u: jax.Array, x3: jax.Array, *, br: int = 128, bb: int = 128,
                 bi: int = 128, ba: int = 1,
                 interpret: bool = False) -> jax.Array:
    """out (A, R, B) = einsum('rn,anb->arb', u, x3).  ``ba`` must divide A;
    the other blocks are the whole dim or a TPU-legal tile."""
    a, i, b = x3.shape
    r, i2 = u.shape
    assert i == i2, (u.shape, x3.shape)
    assert a % ba == 0, (x3.shape, ba)
    grid = (a // ba, pl.cdiv(r, br), pl.cdiv(b, bb), pl.cdiv(i, bi))
    return pl.pallas_call(
        functools.partial(_ttm_kernel, ba=ba, i_total=i, bi=bi),
        name="ttm_interior",
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bi), lambda aa, rr, bbb, ii: (rr, ii)),
            pl.BlockSpec((ba, bi, bb), lambda aa, rr, bbb, ii: (aa, ii, bbb)),
        ],
        out_specs=pl.BlockSpec((ba, br, bb),
                               lambda aa, rr, bbb, ii: (aa, rr, bbb)),
        out_shape=jax.ShapeDtypeStruct((a, r, b), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(u, x3)
