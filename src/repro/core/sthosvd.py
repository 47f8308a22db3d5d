"""Mode-wise flexible st-HOSVD (a-Tucker Alg. 2) and coarse-grained variants.

These are the legacy per-call entry points, kept as adapters with no
algorithm of their own: each maps its arguments onto a
:class:`~repro.core.api.TuckerConfig`, plans it, and runs the plan's
recorded path (``execute(record=True)``: the plan's own sweep, per-mode
wall-clock in the trace), reporting the plan's selector time as
``select_overhead_s``.  For amortized/batched execution use
:mod:`repro.core.api` instead.

``methods`` accepts:
  - "auto"              → adaptive selector (decision tree, cost-model fallback)
  - "eig"/"als"/"svd"   → coarse-grained single solver (paper baselines)
  - sequence per mode   → explicit mode-wise schedule, e.g. ("eig","als","als")
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from . import tensor_ops as T
from .solvers import ALS, DEFAULT_ALS_ITERS, EIG, SVD


@dataclass
class TuckerTensor:
    """Result of a Tucker decomposition:  X ≈ G ×_1 U^(1) ··· ×_N U^(N)."""
    core: jax.Array
    factors: list[jax.Array]          # factors[n]: (I_n, R_n)

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(u.shape[0] for u in self.factors)

    @property
    def ranks(self) -> tuple[int, ...]:
        return tuple(self.core.shape)

    def reconstruct(self) -> jax.Array:
        return T.reconstruct(self.core, self.factors)

    def rel_error(self, x: jax.Array) -> jax.Array:
        return T.rel_error(x, self.core, self.factors)

    @property
    def n_elements(self) -> int:
        return int(self.core.size + sum(u.size for u in self.factors))

    @property
    def compression_ratio(self) -> float:
        return float(math.prod(self.shape)) / float(self.n_elements)


@dataclass
class ModeTrace:
    mode: int
    method: str
    i_n: int
    r_n: int
    j_n: int
    seconds: float             # measured wall-clock (0.0 inside fused sweeps)
    backend: str = "matfree"   # ops backend the solve ran on
    predicted_s: float = 0.0   # plan-time prediction from a calibrated cost
                               # model (0.0 = uncalibrated) — compare with
                               # ``seconds`` for predicted-vs-actual drift
    tail_err: float = 0.0      # discarded energy at this step as a fraction
                               # of ||X||² (rank-adaptive executions only;
                               # 0.0 = not measured).  Flows into the tune
                               # store as the achieved-error label.

    @property
    def delta_s(self) -> float:
        """Predicted-vs-actual drift: ``seconds - predicted_s`` (positive =
        slower than the calibrated model expected).  Only meaningful when
        both sides are real — a fused sweep has no per-step ``seconds`` and
        an uncalibrated plan no ``predicted_s``."""
        return self.seconds - self.predicted_s


@dataclass
class SthosvdResult:
    tucker: TuckerTensor
    trace: list[ModeTrace] = field(default_factory=list)
    select_overhead_s: float = 0.0
    error_bound: float | None = None  # rank-adaptive executions: guaranteed
                                      # relative-error upper bound
                                      # sqrt(Σ_n tail_err_n) from the HOSVD
                                      # inequality; None for fixed-rank runs

    @property
    def methods(self) -> tuple[str, ...]:
        return tuple(t.method for t in sorted(self.trace, key=lambda t: t.mode))

    def report(self) -> str:
        """Per-step execution report in schedule order: solver, problem
        size, measured seconds, and — when a calibrated cost model priced
        the plan — predicted seconds and the drift, so order-search wins
        (and calibration rot) are visible in traces, not just benches."""
        predicted = any(t.predicted_s for t in self.trace)
        head = "step  mode method backend    I     R     J    seconds"
        if predicted:
            head += "  predicted    delta"
        lines = [head]
        for k, t in enumerate(self.trace):
            row = (f"{k:>4}  {t.mode:>4} {t.method:>6} {t.backend:>8} "
                   f"{t.i_n:>5} {t.r_n:>5} {t.j_n:>5} {t.seconds:>9.4f}")
            if predicted:
                row += f" {t.predicted_s:>10.4f} {t.delta_s:>+8.4f}"
            lines.append(row)
        total_s = sum(t.seconds for t in self.trace)
        total = f"total{'':>38}{total_s:>9.4f}"
        if predicted:
            total_p = sum(t.predicted_s for t in self.trace)
            total += f" {total_p:>10.4f} {total_s - total_p:>+8.4f}"
        lines.append(total)
        return "\n".join(lines)


def sthosvd(
    x: jax.Array,
    ranks: Sequence[int],
    methods: str | Sequence[str] = "auto",
    *,
    selector: Callable[..., str] | None = None,
    mode_order: Sequence[int] | str | None = None,
    als_iters: int = DEFAULT_ALS_ITERS,
    impl: str = "matfree",
    memory_cap_bytes: int | None = None,
) -> SthosvdResult:
    """Flexible st-HOSVD (Alg. 2).  Returns factors, core, per-mode trace.

    ``mode_order`` defaults to the paper's 1..N sweep; adaptive shrink-ratio
    ordering (beyond-paper, DESIGN.md §9.3) is available via
    ``mode_order="shrink"``, and the exact DP schedule search (order AND
    per-step solver, optionally under ``memory_cap_bytes``) via
    ``mode_order="opt"`` (see :mod:`repro.core.schedule_opt`).

    ``memory_cap_bytes`` is the hard plan-time ceiling on each step's
    modeled peak working set; infeasible schedules raise ``MemoryCapError``
    naming the binding step before anything is allocated.

    ``impl`` names an ops backend (``matfree`` | ``explicit`` | ``pallas`` |
    custom-registered) or ``"auto"`` for the platform default.
    """
    from .api import TuckerConfig
    return _run_legacy(x, TuckerConfig(
        ranks=ranks, methods=methods, mode_order=mode_order,
        als_iters=als_iters, impl=impl, memory_cap_bytes=memory_cap_bytes),
        selector)


def _run_legacy(x, cfg, selector) -> SthosvdResult:
    """What every legacy entry point does with its ``TuckerConfig``: plan
    it, run the plan's recorded path (per-mode wall-clock in the trace),
    and report the plan's selector time as ``select_overhead_s``."""
    from .api import plan
    x = jnp.asarray(x)
    p = plan(x.shape, x.dtype, cfg, selector=selector)
    res = p.execute(x, record=True)
    res.select_overhead_s = p.select_seconds
    return res


# Coarse-grained baselines (paper Sec. VI) -----------------------------------

def sthosvd_eig(x, ranks, **kw) -> SthosvdResult:
    return sthosvd(x, ranks, methods=EIG, **kw)


def sthosvd_als(x, ranks, **kw) -> SthosvdResult:
    return sthosvd(x, ranks, methods=ALS, **kw)


def sthosvd_svd(x, ranks, **kw) -> SthosvdResult:
    return sthosvd(x, ranks, methods=SVD, **kw)
