"""Measurement producers: offline sampling harness + online trace harvester.

Offline (:func:`collect`): random tensors, log-uniform dims/ranks (paper
Sec. IV-B; covers the asymmetric one-huge-mode shapes where the EIG/ALS
crossover lives), each mode timed with BOTH solvers through each requested
ops backend — the paired records are exactly what labeling needs.  Named
``tensors`` add every step of their st-HOSVD sweep at stated ranks, so a
calibration also covers the step shapes a deployment runs.

Online (:func:`recording` / :func:`harvest_result`): every executed
``TuckerPlan`` already produces per-mode ``ModeTrace`` records; inside a
``recording()`` context (or with ``plan.execute(record=True)``) those traces
carry real wall-clock and are converted into :class:`Measurement` rows —
production traffic improves the selector for free.  Online records are
one-sided (only the solver the plan chose ran), so they sharpen the store
wherever offline coverage or OTHER plans supply the opposing method.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterable, Sequence

import numpy as np

from ..core.selector import extract_features
from ..core.solvers import DEFAULT_ALS_ITERS
from .records import (
    COLLECT,
    HARVEST,
    Measurement,
    RecordStore,
    device_fingerprint,
)

#: tiny preset for CI — a handful of tensors, one backend, dims small enough
#: that the whole collect→train loop finishes in well under a minute
SMOKE = dict(n_tensors=8, dim_range=(8, 40), backends=("matfree",),
             orders=(3,), reps=1)


#: longest mode whose EIG is timed, per platform: on the TPU the compile of
#: an I_n×I_n ``eigh`` grows steeply with I_n (about 4.5 GB of host memory
#: at 1000, 11 GB at 2000), so the calibrated I³ term prices longer ones
MAX_EIG_DIM = {"tpu": 1024}


def _time_solver(y, mode, rank, method: str, *, impl: str,
                 als_iters: int = DEFAULT_ALS_ITERS, reps: int = 2) -> float:
    import jax

    from ..core.solvers import SOLVERS
    kw = {"num_iters": als_iters} if method == "als" else {}
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(SOLVERS[method](y, mode, rank, impl=impl, **kw))
        best = min(best, time.perf_counter() - t0)
    return best


def collect(
    n_tensors: int = 120,
    dim_range: tuple[int, int] = (10, 192),
    seed: int = 0,
    *,
    orders: Sequence[int] = (3,),
    backends: Sequence[str] = ("matfree",),
    dtype=np.float32,
    als_iters: int = DEFAULT_ALS_ITERS,
    reps: int = 2,
    max_elements: int = 1 << 22,
    tensors: Sequence[tuple[tuple[int, ...], tuple[int, ...]]] = (),
    verbose: bool = False,
) -> list[Measurement]:
    """Time EIG vs ALS per (tensor, mode, backend) → paired Measurements.

    One eig + one als record per point, as in the paper ("the statistics of
    each mode constitute a record"), stratified across ``backends`` and
    tensor ``orders``.  Warm-up compile is excluded by timing the best of
    ``reps`` runs after a throwaway call.

    ``max_elements`` caps the sampled tensor volume (higher orders would
    otherwise explode: dim_range's top end to the 4th power is terabytes)
    by halving the largest sampled dim until the tensor fits.

    ``tensors`` lists ``(shape, ranks)`` pairs timed on top of the random
    sample, at no cap: each step of the natural-order st-HOSVD sweep, on a
    Gaussian tensor of that step's input shape (the modes before it
    already shrunk to their ranks).

    A mode longer than the platform's :data:`MAX_EIG_DIM` is timed with
    ALS alone.
    """
    import jax
    import jax.numpy as jnp

    from ..core.backend import get_backend
    for b in backends:
        get_backend(b)   # fail fast on unknown names
    rng = np.random.default_rng(seed)
    platform = jax.default_backend()
    device = device_fingerprint()
    dtype_name = str(jnp.dtype(dtype))

    def log_uniform(lo, hi):
        return int(round(np.exp(rng.uniform(np.log(lo), np.log(hi)))))

    out: list[Measurement] = []

    def time_modes(x, ranks, modes):
        for impl in backends:
            for mode in modes:
                i_n, r_n = x.shape[mode], ranks[mode]
                common = dict(platform=platform, backend=impl, device=device,
                              i_n=i_n, r_n=r_n, j_n=x.size // i_n,
                              dtype=dtype_name, order=x.ndim,
                              als_iters=als_iters, source=COLLECT)
                if i_n <= MAX_EIG_DIM.get(platform, i_n):
                    # throwaway to exclude compile time, then measure
                    _time_solver(x, mode, r_n, "eig", impl=impl, reps=1)
                    te = _time_solver(x, mode, r_n, "eig", impl=impl,
                                      reps=reps)
                    out.append(Measurement(method="eig", seconds=te,
                                           **common))
                _time_solver(x, mode, r_n, "als", impl=impl,
                             als_iters=als_iters, reps=1)
                ta = _time_solver(x, mode, r_n, "als", impl=impl,
                                  als_iters=als_iters, reps=reps)
                out.append(Measurement(method="als", seconds=ta, **common))

    for t in range(n_tensors):
        order = int(orders[t % len(orders)])
        dims = [log_uniform(dim_range[0], dim_range[1])
                for _ in range(order)]
        while np.prod(dims) > max_elements and max(dims) > 4:
            k = int(np.argmax(dims))
            dims[k] = max(4, dims[k] // 2)
        if np.prod(dims) > max_elements:
            # even all-4 dims overflow the cap (absurd order): skip rather
            # than allocate a tensor the cap exists to prevent
            if verbose:
                print(f"[tune.collect] skipping order-{order} sample "
                      f"(4^{order} > max_elements)")
            continue
        dims = tuple(dims)
        ranks = tuple(log_uniform(max(1, min(4, d // 2)), max(2, d // 2))
                      for d in dims)
        x = jnp.asarray(rng.standard_normal(dims), dtype=dtype)
        time_modes(x, ranks, range(order))
        if verbose and (t + 1) % 10 == 0:
            print(f"[tune.collect] {t + 1}/{n_tensors} tensors sampled "
                  f"({len(out)} records)")
    key = jax.random.PRNGKey(seed)
    for shape, ranks in tensors:
        for mode in range(len(shape)):
            step = tuple(ranks[:mode]) + tuple(shape[mode:])
            key, sub = jax.random.split(key)
            x = jax.random.normal(sub, step, dtype)
            time_modes(x, ranks, (mode,))
            del x
        if verbose:
            print(f"[tune.collect] sweep of {shape} at ranks {ranks} timed "
                  f"({len(out)} records)")
    return out


def collect_into(store: RecordStore, **kw) -> int:
    """``collect()`` straight into a store; returns records appended."""
    return store.append(collect(**kw))


def collect_samples(
    n_tensors: int = 120,
    dim_range: tuple[int, int] = (10, 192),
    seed: int = 0,
    order: int = 3,
    dtype=np.float32,
    verbose: bool = False,
):
    """Legacy array API: (features, labels, times) on the matfree backend —
    the pre-flywheel signature kept for existing call sites
    (benchmarks/paper_figs.py, repro.core.selector re-export)."""
    ms = collect(n_tensors, dim_range, seed, orders=(order,), dtype=dtype,
                 verbose=verbose)
    feats, labels, times = [], [], []
    for te, ta in zip(ms[::2], ms[1::2]):   # collect() emits (eig, als) pairs
        feats.append(extract_features(te.i_n, te.r_n, te.j_n))
        labels.append(0 if te.seconds <= ta.seconds else 1)
        times.append((te.seconds, ta.seconds))
    return np.array(feats), np.array(labels), np.array(times)


# ---------------------------------------------------------------------------
# Online harvesting: executed-plan traces → training records
# ---------------------------------------------------------------------------

class RecordSink:
    """In-memory accumulator the plan layer feeds timed traces into while a
    :func:`recording` context is active."""

    def __init__(self):
        self.measurements: list[Measurement] = []

    def add_traces(self, traces, *, platform: str, dtype: str,
                   order: int, als_iters: int = DEFAULT_ALS_ITERS) -> int:
        ms = measurements_from_traces(traces, platform=platform, dtype=dtype,
                                      order=order, als_iters=als_iters)
        self.measurements.extend(ms)
        return len(ms)

    def flush(self, store: RecordStore) -> int:
        n = store.append(self.measurements)
        self.measurements.clear()
        return n


_SINKS: list[RecordSink] = []


def active_sink() -> RecordSink | None:
    """The innermost active recording sink (None outside any context).
    Checked by ``TuckerPlan.execute`` — via ``sys.modules`` so plans that
    never meet the tune subsystem pay nothing."""
    return _SINKS[-1] if _SINKS else None


@contextlib.contextmanager
def recording(store: RecordStore | str | None = None):
    """Process-wide harvest context: every ``TuckerPlan.execute`` inside it
    runs the timed (eager) path and its per-mode wall-clock lands in the
    yielded :class:`RecordSink` — flushed to ``store`` on exit if given.

        with tune.recording(store):
            plan.execute(x)          # production call, now also a sample
    """
    if isinstance(store, (str, bytes)) or hasattr(store, "__fspath__"):
        store = RecordStore(store)
    sink = RecordSink()
    _SINKS.append(sink)
    try:
        yield sink
    finally:
        _SINKS.remove(sink)
        if store is not None:
            sink.flush(store)


def measurements_from_traces(traces, *, platform: str, dtype: str,
                             order: int,
                             als_iters: int = DEFAULT_ALS_ITERS,
                             ) -> list[Measurement]:
    """Convert timed ``ModeTrace`` records into harvest Measurements.

    Traces with no real timing (``seconds <= 0`` — e.g. from the fused
    jitted sweep, where per-step time is unobservable) and solver families
    outside EIG/ALS/RAND are skipped: only rows a trainer can label
    against belong in the store.

    Each row carries the trace's plan-time ``predicted_s`` (when a
    calibrated cost model priced the schedule), so decisions made by the
    schedule optimizer — which solver the DP picked and what it believed
    the step would cost — become auditable records the flywheel can check
    for drift (``python -m repro.tune report``).  Rank-adaptive ``rand``
    traces additionally carry their measured fractional tail energy, which
    lands as the row's ``rel_err`` achieved-error label — so future
    selectors can learn speed AND accuracy.
    """
    device = device_fingerprint()
    out = []
    for t in traces:
        if t.seconds <= 0.0 or t.method not in ("eig", "als", "rand"):
            continue
        out.append(Measurement(
            platform=platform, backend=t.backend, device=device,
            i_n=t.i_n, r_n=t.r_n, j_n=t.j_n, method=t.method,
            seconds=float(t.seconds), dtype=dtype, order=order,
            als_iters=als_iters, source=HARVEST,
            predicted_s=float(getattr(t, "predicted_s", 0.0)),
            rel_err=float(getattr(t, "tail_err", 0.0))))
    return out


def harvest_result(result, store: RecordStore | None = None, *,
                   platform: str | None = None, dtype: str = "float32",
                   als_iters: int = DEFAULT_ALS_ITERS) -> list[Measurement]:
    """Harvest one ``SthosvdResult`` (from ``plan.execute(record=True)`` or
    a legacy entry point, whose traces always carry wall-clock) into
    Measurements; appended to ``store`` when given."""
    import jax
    platform = platform or jax.default_backend()
    order = len({t.mode for t in result.trace})
    ms = measurements_from_traces(result.trace, platform=platform,
                                  dtype=dtype, order=order,
                                  als_iters=als_iters)
    if store is not None:
        store.append(ms)
    return ms


def harvest_results(results: Iterable, store: RecordStore, **kw) -> int:
    """Batch :func:`harvest_result`; returns total records appended."""
    return sum(len(harvest_result(r, store, **kw)) for r in results)
