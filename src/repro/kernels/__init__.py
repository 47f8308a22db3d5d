"""Pallas TPU kernels for a-Tucker's matricization-free hot spots.

Kernels (each with BlockSpec VMEM tiling; validated vs ref.py in
tests/test_kernels.py via interpret mode):
  matmul.matmul        — tiled MXU GEMM (boundary-mode TTM)
  ttm.ttm_interior     — interior-mode batched-GEMM TTM
  ttt.ttt_nt           — TTT / Gram over the merged outer+inner dims
  ttt.ttt_tn           — last-mode TTT / Gram, contracting the outer dims

ops.py carries the jit'd public wrappers that pick views and tiles.
"""

from . import ops, ref
from .matmul import matmul
from .ttm import ttm_interior
from .ttt import ttt_nt, ttt_tn

__all__ = ["matmul", "ops", "ref", "ttm_interior", "ttt_nt", "ttt_tn"]
