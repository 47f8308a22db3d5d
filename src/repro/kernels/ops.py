"""jit'd public wrappers for the a-Tucker Pallas kernels.

These are the primitives behind the ``pallas`` ops backend
(:mod:`repro.core.backend`): ``TuckerConfig(impl="pallas")`` — or
``impl="auto"`` on TPU — routes every TTM/TTT/Gram of a plan's sweep through
this module.

Dispatch mirrors the paper's Fig. 4 structure, on views that only merge
adjacent axes:
  mode == 0    → (I_n, B) view:  TTM = one GEMM u @ X,   Gram/TTT = X Yᵀ
  mode == N-1  → (A, I_n) view:  TTM = one GEMM X @ uᵀ,  Gram/TTT = Xᵀ Y
  interior     → (A, I_n, B) view: batched GEMM / TTT over the merged A
No view puts a unit or tiny axis on the lanes: a tensor whose last axis is
narrower than a lane tile is first transposed so a wide axis is last
(:func:`_lane_order`), one relayout copy instead of a 128-fold padded one.
No operand is padded either: the kernels take ragged edge blocks and zero
the out-of-range part of a contraction in-kernel, so the only HBM buffers
are the tensor, its view (XLA copies it when a merge crosses the tiled
minor axes) and the result.

Tiles: an output or target-mode axis takes its whole extent up to
``_TILE`` (always legal) and ``_TILE`` tiles beyond; a streamed (reduced
or batch) axis takes as much as fits ``_BLOCK_BYTES`` against its block's
padded cross-section — multiples of 128 on lanes and 8 on sublanes.

``interpret`` defaults to True off-TPU so the same code path validates on
CPU (Pallas interpreter) and compiles to Mosaic on the TPU target.
"""

from __future__ import annotations

import math
from functools import partial

import jax

from .matmul import matmul
from .ttm import ttm_interior
from .ttt import ttt_nt, ttt_tn

_LANE, _SUBLANE = 128, 8
#: cap on a non-streamed block axis (a multiple of _LANE, so always legal)
_TILE = 512
#: one streamed input block, padded, at fp32: double-buffered inputs plus
#: the resident output tile stay inside the default scoped VMEM
_BLOCK_BYTES = 2 << 20


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _fit(dim: int) -> int:
    """Block extent along a non-streamed axis: whole, or ``_TILE`` tiles."""
    return dim if dim <= _TILE else _TILE


def _stream(dim: int, cross: int, align: int) -> int:
    """Block extent along a streamed axis whose block has padded
    cross-section ``cross`` elements: the whole axis when it fits
    ``_BLOCK_BYTES``, else the largest ``align`` multiple that does."""
    n = max(align, _BLOCK_BYTES // (4 * cross) // align * align)
    return dim if dim <= n else n


def _batch(a: int, slab: int) -> int:
    """Slices of the merged outer axis per block: the largest divisor of
    ``a`` whose ``slab``-element slices fit ``_BLOCK_BYTES``."""
    cap = max(1, _BLOCK_BYTES // (4 * slab))
    return max(d for d in range(1, min(a, cap) + 1) if a % d == 0)


def _split(shape: tuple[int, ...], mode: int) -> tuple[int, int, int]:
    return (math.prod(shape[:mode]), shape[mode], math.prod(shape[mode + 1:]))


def _lane_order(shape: tuple[int, ...], mode: int) -> tuple[int, ...] | None:
    """Axis order that puts a lane-wide axis last, for a tensor whose last
    axis is narrower than a lane tile: the other narrow axes lead, the wide
    ones follow, and a narrow target ``mode`` sits just before the last wide
    axis, so its view is (A, I_n, B) with B wide and nothing merged across
    the tiled minor axes.  None when the last axis is wide, or none is."""
    if shape[-1] >= _LANE or all(d < _LANE for d in shape):
        return None
    narrow = [m for m, d in enumerate(shape) if d < _LANE and m != mode]
    wide = [m for m, d in enumerate(shape) if d >= _LANE or m == mode]
    if shape[mode] < _LANE:
        wide.remove(mode)
        wide.insert(len(wide) - 1, mode)
    return tuple(narrow + wide)


@partial(jax.jit, static_argnames=("mode", "interpret"))
def ttm(x: jax.Array, u: jax.Array, mode: int, *, interpret: bool | None = None) -> jax.Array:
    """Mode-n TTM via Pallas.  u: (R, I_mode).  Returns fp32."""
    interpret = _default_interpret() if interpret is None else interpret
    r, i = u.shape
    assert x.shape[mode] == i, (x.shape, u.shape, mode)
    order = _lane_order(x.shape, mode)
    if order is not None:
        y = ttm(x.transpose(order), u, order.index(mode), interpret=interpret)
        return y.transpose(tuple(order.index(m) for m in range(x.ndim)))
    out_shape = x.shape[:mode] + (r,) + x.shape[mode + 1:]
    a, _, b = _split(x.shape, mode)
    bi, br = _fit(i), _fit(r)
    if mode == 0:
        bn = _stream(b, _round_up(max(bi, br), _SUBLANE), _LANE)
        y = matmul(u, x.reshape(i, b), bm=br, bn=bn, bk=bi,
                   interpret=interpret)
    elif mode == x.ndim - 1:
        bm = _stream(a, _round_up(max(bi, br), _LANE), _SUBLANE)
        y = matmul(x.reshape(a, i), u.T, bm=bm, bn=br, bk=bi,
                   interpret=interpret)
    else:
        bb = _stream(b, _round_up(max(bi, br), _SUBLANE), _LANE)
        ba = _batch(a, _round_up(max(bi, br), _SUBLANE)
                    * _round_up(bb, _LANE))
        y = ttm_interior(u, x.reshape(a, i, b), br=br, bb=bb, bi=bi, ba=ba,
                         interpret=interpret)
    return y.reshape(out_shape)


@partial(jax.jit, static_argnames=("mode", "interpret"))
def ttt(x: jax.Array, y: jax.Array, mode: int, *, interpret: bool | None = None) -> jax.Array:
    """z (I_mode, R_mode) = contraction of x, y over all modes but ``mode``."""
    interpret = _default_interpret() if interpret is None else interpret
    order = _lane_order(x.shape, mode)
    if order is not None:
        return ttt(x.transpose(order), y.transpose(order), order.index(mode),
                   interpret=interpret)
    a, i, b = _split(x.shape, mode)
    r = y.shape[mode]
    bi, br = _fit(i), _fit(r)
    if mode == x.ndim - 1:
        ba = _stream(a, _round_up(max(bi, br), _LANE), _SUBLANE)
        return ttt_tn(x.reshape(a, i), y.reshape(a, r), bi=bi, br=br, ba=ba,
                      interpret=interpret)
    rows = _round_up(max(bi, br), _SUBLANE)
    bb = _stream(b, rows, _LANE)
    if mode == 0:
        return ttt_nt(x.reshape(i, b), y.reshape(r, b), bi=bi, br=br, bb=bb,
                      interpret=interpret)
    ba = _batch(a, rows * _round_up(bb, _LANE))
    return ttt_nt(x.reshape(a, i, b), y.reshape(a, r, b), bi=bi, br=br,
                  bb=bb, ba=ba, interpret=interpret)


def gram(x: jax.Array, mode: int, *, interpret: bool | None = None) -> jax.Array:
    """S (I_mode, I_mode) = Y_(n) Y_(n)ᵀ without unfolding: TTT with y ≡ x."""
    return ttt(x, x, mode, interpret=interpret)
