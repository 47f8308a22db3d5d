"""Autotune subsystem: the measurement flywheel behind the adaptive selector.

The paper's selector is trained once, offline (Sec. IV-B).  This package
turns that into a loop:

  * :mod:`repro.tune.records` — append-only JSONL measurement store
    (platform + backend + device fingerprint, (I_n, R_n, J_n), method,
    seconds) with dedup/merge/digest.
  * :mod:`repro.tune.collect` — offline sampling harness across registered
    ops backends and tensor orders, plus the ONLINE harvester:
    ``recording()`` / ``plan.execute(record=True)`` convert the ModeTrace
    records of production executions into training records for free.
  * :mod:`repro.tune.train` — (platform, backend)-stratified decision
    trees with embedded provenance metadata, resolved by
    ``repro.core.selector.default_selector`` per (platform, backend) with
    graceful fallback.
  * :mod:`repro.tune.calibrate` — least-squares fit of the symbolic
    f_eig/f_qr constants (and seconds-per-FLOP scales) of the Eq. 4/5
    cost model per backend, hardware-calibrating the selector's
    out-of-range guardrail.

CLI: ``python -m repro.tune {collect | harvest | train | calibrate |
report}``.
"""

from .calibrate import calibrate_store, fit_cost_model
from .collect import (
    active_sink,
    collect,
    collect_into,
    harvest_result,
    harvest_results,
    recording,
)
from .records import Measurement, RecordStore, default_store_path
from .train import labeled_examples, train_selector, train_stratified

__all__ = [
    "Measurement", "RecordStore", "active_sink", "calibrate_store",
    "collect", "collect_into", "default_store_path", "fit_cost_model",
    "harvest_result", "harvest_results", "labeled_examples", "recording",
    "train_selector", "train_stratified",
]
