"""Hardware calibration of the Eq. 4/5 cost model from measured records.

The paper leaves the LAPACK-kernel constants f_eig/f_qr symbolic; the
textbook values (9n³, 2mn²−(2/3)n³) assume every FLOP costs the
same, which no real BLAS does — eigendecomposition FLOPs on a 1-core CPU
are far slower than GEMM FLOPs, and each ops backend shifts the balance
again.  This module fits, per (platform, backend), a least-squares
decomposition of measured seconds onto the model's term structure:

    eig seconds ≈ o_e + α_e·(I²J + 2IRJ)       + β_e·I³
    als seconds ≈ o_a + α_a·(GEMM-family terms) + γ_a·(iters·QR(I,R))

which recovers c_eig = β_e/α_e, c_qr = γ_a/α_a and —
because the fit is against *seconds* — the per-FLOP scales α_e, α_a and
per-solve dispatch overheads o_e, o_a that make
``CostModel.predict_seconds`` real wall-clock and ``predicted_best`` a
seconds comparison instead of a FLOP comparison.  (The intercepts matter:
on small modes kernel-launch overhead dominates, and ALS launches far more
kernels per solve than EIG — a pure FLOP model gets exactly the
small-problem regime wrong.)  The result feeds the trained selector's
out-of-range guardrail, so the paper's huge-mode regime is decided by
hardware-calibrated constants instead of textbook ones.

The GEMM-family columns count each contraction at the width the
platform's matrix unit computes (``CostModel.lane``: 128 on the TPU, 1
elsewhere), so the fitted per-FLOP scales are per padded FLOP and the
saved model prices with the same lane.

A constant whose fitted coefficient comes back non-positive (collinear or
starved design) silently keeps its textbook value — calibration degrades
toward the default, never past it.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable

import numpy as np

from ..core.cost_model import CostModel, lane_for
from ..core.selector import calibration_path
from .records import Measurement, RecordStore

#: minimum records per method before a fit is attempted
MIN_RECORDS = 8


def _eig_basis(i, r, j, lane):
    """(intercept, GEMM-family, I³) columns of the Eq. 4 decomposition."""
    gemm = CostModel(c_eig=0.0, lane=lane).eig_flops(i, r, j)
    return np.array([1.0, gemm, float(i) ** 3])


def _als_basis(i, r, j, iters, lane):
    """(intercept, GEMM-family, QR-count) columns of the Eq. 5
    decomposition — the QR column carries one Householder count at
    c_qr = 1 per iteration."""
    gemm = CostModel(c_qr=0.0, lane=lane).als_flops(i, r, j, iters)
    return np.array([1.0, gemm, iters * CostModel().f_qr(i, r)])


def _nonneg_lstsq(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """lstsq with a poor man's non-negativity: columns whose coefficient
    comes back negative are dropped (zeroed) and the rest refit, so one
    collinear term cannot poison the whole calibration."""
    cols = list(range(a.shape[1]))
    coef = np.zeros(a.shape[1])
    for _ in range(a.shape[1]):
        c, *_ = np.linalg.lstsq(a[:, cols], b, rcond=None)
        if (c >= 0).all():
            coef[cols] = c
            return coef
        cols = [cols[k] for k in range(len(cols)) if c[k] >= 0]
        if not cols:
            return coef
    coef[cols] = np.linalg.lstsq(a[:, cols], b, rcond=None)[0]
    return np.maximum(coef, 0.0)


def fit_cost_model(measurements: Iterable[Measurement],
                   min_records: int = MIN_RECORDS) -> CostModel | None:
    """Fit a calibrated :class:`CostModel` from eig/als measurements.

    Returns None when either method has fewer than ``min_records`` deduped
    records (a starved fit is worse than the textbook default).  Records
    should come from ONE (platform, backend) stratum — mixing hardware
    mixes the very constants being fitted, so records from more than one
    platform raise ``ValueError``.  The fit counts contractions at the
    records' platform's lane (:func:`~repro.core.cost_model.lane_for`) and
    the model keeps it.
    """
    measurements = list(measurements)
    platforms = {m.platform for m in measurements}
    if len(platforms) > 1:
        raise ValueError("fit_cost_model needs one platform's records, got "
                         f"{sorted(platforms)}")
    lane = lane_for(platforms.pop()) if platforms else 1
    eig, als = {}, {}
    for m in measurements:
        slot = eig if m.method == "eig" else als if m.method == "als" else None
        if slot is None:
            continue
        cur = slot.get(m.problem_key())
        if cur is None or m.seconds < cur.seconds:
            slot[m.problem_key()] = m
    if len(eig) < min_records or len(als) < min_records:
        return None

    a_e = np.stack([_eig_basis(m.i_n, m.r_n, m.j_n, lane)
                    for m in eig.values()])
    b_e = np.array([m.seconds for m in eig.values()])
    ce = _nonneg_lstsq(a_e, b_e)

    a_a = np.stack([_als_basis(m.i_n, m.r_n, m.j_n, m.als_iters, lane)
                    for m in als.values()])
    b_a = np.array([m.seconds for m in als.values()])
    ca = _nonneg_lstsq(a_a, b_a)

    o_e, a_e1, b_e1 = ce
    o_a, a_a1, g_a1 = ca
    if a_e1 <= 0 and a_a1 <= 0:
        return None   # no usable per-FLOP signal — not a calibration
    default = CostModel()
    # constants are RATIOS to the GEMM coefficient; a zeroed GEMM column
    # (degenerate fit) keeps every dependent constant at textbook
    c_eig = b_e1 / a_e1 if a_e1 > 0 and b_e1 > 0 else default.c_eig
    c_qr = g_a1 / a_a1 if a_a1 > 0 and g_a1 > 0 else default.c_qr
    return CostModel(c_eig=float(c_eig), c_qr=float(c_qr),
                     eig_scale=float(a_e1) if a_e1 > 0 else 1.0,
                     als_scale=float(a_a1) if a_a1 > 0 else 1.0,
                     eig_overhead_s=float(max(o_e, 0.0)),
                     als_overhead_s=float(max(o_a, 0.0)),
                     lane=lane, source="calibrated")


def calibrate_store(store: RecordStore, *, platform: str | None = None,
                    model_dir=None,
                    min_records: int = MIN_RECORDS) -> dict[str, dict]:
    """Fit + save one calibration file per (platform, backend) stratum in
    the store.  Returns {written path: cost-model dict}."""
    if platform is None:
        import jax
        platform = jax.default_backend()
    records = store.filter(platform=platform)
    written: dict[str, dict] = {}
    for backend in sorted({m.backend for m in records}):
        cm = fit_cost_model([m for m in records if m.backend == backend],
                            min_records=min_records)
        if cm is None:
            continue
        path = calibration_path(platform, backend)
        if model_dir is not None:
            path = Path(model_dir) / path.name
        path.parent.mkdir(parents=True, exist_ok=True)
        doc = {**cm.to_dict(), "platform": platform, "backend": backend,
               "n_records": len([m for m in records if m.backend == backend]),
               "store_digest": store.digest()}
        path.write_text(json.dumps(doc, indent=1))
        written[str(path)] = doc
    return written
