"""Tucker-decomposition variants beyond st-HOSVD (paper §II-B / §VIII).

The paper focuses on st-HOSVD and names t-HOSVD and HOOI as the natural
extensions ("owning to the similar algorithm structure, the proposed ideas
and optimizations can also be extended") — both are built on the same
matricization-free solvers and the same adaptive selector:

  * t-HOSVD: every factor computed from the ORIGINAL tensor (no sequential
    shrinking) — more flops, sometimes preferred for parallel factor
    computation.
  * HOOI: higher-order orthogonal iteration — alternating refinement of the
    factors, initialized from st-HOSVD (the standard pairing).  Each inner
    subproblem is a mode solve of the partially-projected tensor, so the
    EIG/ALS switch and the selector apply verbatim.

The algorithms live in :mod:`repro.core.plan` (``sweep_thosvd``,
``sweep_hooi``); these entry points are adapters over
``plan(..., TuckerConfig(variant=...)).execute(x, record=True)``, exactly
like :func:`repro.core.sthosvd.sthosvd`.
"""

from __future__ import annotations

import jax

from .api import TuckerConfig
from .solvers import DEFAULT_ALS_ITERS
from .sthosvd import SthosvdResult, _run_legacy


def thosvd(x: jax.Array, ranks, methods: str = "auto", *,
           selector=None, als_iters: int = DEFAULT_ALS_ITERS,
           impl: str = "matfree",
           memory_cap_bytes: int | None = None) -> SthosvdResult:
    """Truncated HOSVD: factors from the original tensor, one projection.

    ``memory_cap_bytes`` fails the plan loudly when any mode solve's modeled
    peak exceeds it — t-HOSVD has no order freedom, so the cap can only be
    met by a smaller solver (or not at all)."""
    return _run_legacy(x, TuckerConfig(
        ranks=ranks, variant="thosvd", methods=methods, als_iters=als_iters,
        impl=impl, memory_cap_bytes=memory_cap_bytes), selector)


def hooi(x: jax.Array, ranks, *, n_iters: int = 3, methods: str = "auto",
         selector=None, als_iters: int = DEFAULT_ALS_ITERS,
         impl: str = "matfree", mode_order=None,
         memory_cap_bytes: int | None = None) -> SthosvdResult:
    """Higher-order orthogonal iteration, st-HOSVD-initialized.

    Per sweep and mode: project x on all OTHER factors, then solve the mode
    with the flexible (selector-driven) solver.  Error is non-increasing in
    exact arithmetic; typically converges in 2–5 sweeps.

    ``mode_order`` (incl. ``"shrink"``/``"opt"``) orders the st-HOSVD INIT
    sweep — refinement sweeps always cycle 0..N-1; ``memory_cap_bytes``
    caps every step (init and refinements) at plan time.
    """
    return _run_legacy(x, TuckerConfig(
        ranks=ranks, variant="hooi", methods=methods, mode_order=mode_order,
        als_iters=als_iters, hooi_iters=n_iters, impl=impl,
        memory_cap_bytes=memory_cap_bytes), selector)
