"""Static solver schedules for the plan/execute Tucker front door.

The paper's flexible algorithms pick a solver per mode at runtime; here the
same selection happens ONCE, ahead of time, against the (statically known)
shapes each mode solve will see.  The result is a tuple of :class:`ModeStep`
records — mode, solver, the (I_n, R_n, J_n) triple the selector saw, plus
modeled FLOPs (cost_model Eq. 4/5) and peak working-set bytes — which is

  * the single dispatch point for all three variants (st-HOSVD shrinks the
    tensor between steps, t-HOSVD solves every mode on the original tensor,
    HOOI refines from an st-HOSVD init), replacing the per-variant copies of
    the selector/dispatch logic, and
  * fully static, so an entire sweep can be compiled as ONE jitted program
    and vmapped over a batch axis (see :mod:`repro.core.api`).

Each variant is defined once, as a ``sweep_*`` pure function (the
:data:`SWEEPS` table).  The plan compiles it whole; the recorded path runs
the same function unjitted with a :class:`StepTimer` as its per-step solve,
which gives every mode solve its own span and wall-clock in the trace.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Callable, Sequence

import jax

from .. import chaos as _chaos
from ..obs import drift as _drift
from ..obs import trace as _obs
from . import tensor_ops as T
from .backend import get_backend
from .cost_model import als_flops, eig_flops, rand_flops, svd_flops
from .errors import NumericalError
from .solvers import (ALS, DEFAULT_ALS_ITERS, DEFAULT_OVERSAMPLE,
                      DEFAULT_POWER_ITERS, RAND, SOLVERS, SolveResult)

VARIANTS = ("sthosvd", "thosvd", "hooi")


@dataclass(frozen=True)
class ModeStep:
    """One frozen mode solve: which solver runs on which (sub)problem,
    through which ops backend.

    For sharded schedules (``backend="sharded"``) two extra fields freeze
    the distribution decision: ``shard_mode`` is the tensor mode the input
    is sharded on while this step runs (``None`` = fully replicated — the
    shrunk tensor no longer divides over the mesh), and ``n_shards`` is the
    device count the step's slab is split across (1 when replicated).
    ``peak_bytes`` is then a PER-DEVICE figure: the sharded I/O slabs divide
    by ``n_shards`` while replicated solver scratch does not.

    ``group`` marks mode-parallel execution: consecutive steps sharing a
    non-None group id compute their factors concurrently from the SAME
    un-shrunk tensor (their ``j_n`` reflects the group-entry shape, not the
    sequential shrink) and truncate together in one fused multi-TTM.
    ``None`` (the back-compat default) is a sequential singleton.  Group
    members all record the GROUP's modeled peak (the shared input slab plus
    every member's concurrent solver scratch) as their ``peak_bytes``.

    The RANK POLICY fields make a step rank-*adaptive* (error-targeted
    plans, see :class:`repro.core.api.TuckerConfig` ``error_target``):
    ``rank_grid`` is the ascending tuple of candidate ranks the executed
    sketch may settle on (``r_n`` is then the sizing CAP — the largest
    candidate — so FLOPs/peak stay conservative), and ``tau`` is this
    mode's squared error budget as a fraction of ``||X||²`` (the HOSVD
    bound ``||X-X̂||² ≤ Σ_n τ_n²`` equi-partitioned: ``tau = ε²/N``).
    Fixed-rank steps keep the defaults (``None``/``0.0``) and serialize
    byte-identically to pre-rank-policy plans.
    """
    mode: int
    method: str          # "eig" | "als" | "svd" | "rand"
    i_n: int             # mode dimension at solve time
    r_n: int             # truncation rank
    j_n: int             # product of the remaining dims at solve time
    flops: float         # modeled solver cost (cost_model Eq. 4/5)
    peak_bytes: int      # modeled peak working set (per device if sharded)
    backend: str = "matfree"   # resolved ops backend (never "auto")
    shard_mode: int | None = None  # mode sharded over the mesh (None = replicated)
    n_shards: int = 1    # devices this step's tensor is split across
    predicted_s: float = 0.0   # predicted wall-clock (0.0 = no calibrated
                               # cost model was available at plan time)
    group: int | None = None   # mode-parallel group id (None = sequential)
    rank_grid: tuple[int, ...] | None = None  # adaptive candidate ranks
    tau: float = 0.0     # squared error budget / ||X||² (adaptive steps only)

    def to_dict(self) -> dict:
        d = {"mode": self.mode, "method": self.method, "i_n": self.i_n,
             "r_n": self.r_n, "j_n": self.j_n, "flops": self.flops,
             "peak_bytes": self.peak_bytes, "backend": self.backend,
             "shard_mode": self.shard_mode, "n_shards": self.n_shards,
             "predicted_s": self.predicted_s, "group": self.group}
        # the rank policy serializes only when present, so fixed-rank plan
        # JSON stays byte-identical to pre-rank-policy writers
        if self.rank_grid is not None:
            d["rank_grid"] = list(self.rank_grid)
            d["tau"] = self.tau
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ModeStep":
        shard_mode = d.get("shard_mode")
        group = d.get("group")
        rank_grid = d.get("rank_grid")
        return cls(mode=int(d["mode"]), method=str(d["method"]),
                   i_n=int(d["i_n"]), r_n=int(d["r_n"]), j_n=int(d["j_n"]),
                   flops=float(d["flops"]), peak_bytes=int(d["peak_bytes"]),
                   backend=str(d.get("backend", "matfree")),
                   shard_mode=None if shard_mode is None else int(shard_mode),
                   n_shards=int(d.get("n_shards", 1)),
                   predicted_s=float(d.get("predicted_s", 0.0)),
                   group=None if group is None else int(group),
                   rank_grid=None if rank_grid is None
                   else tuple(int(r) for r in rank_grid),
                   tau=float(d.get("tau", 0.0)))


class TimedSelector:
    """Wraps a selector callable, accumulating wall-clock spent selecting."""

    def __init__(self, selector: Callable[..., str]):
        self._selector = selector
        self.seconds = 0.0
        self.calls = 0

    def __call__(self, *, i_n: int, r_n: int, j_n: int) -> str:
        t0 = time.perf_counter()
        method = self._selector(i_n=i_n, r_n=r_n, j_n=j_n)
        self.seconds += time.perf_counter() - t0
        self.calls += 1
        return method

    @property
    def cost_model(self):
        """The wrapped selector's (possibly calibrated) cost model, if any."""
        return getattr(self._selector, "cost_model", None)


# ---------------------------------------------------------------------------
# Schedule resolution (selection moved out of the hot loop)
# ---------------------------------------------------------------------------

def resolve_mode_order(shape: Sequence[int], ranks: Sequence[int],
                       mode_order) -> list[int]:
    n = len(shape)
    if mode_order is None:
        return list(range(n))
    if mode_order == "opt":
        raise ValueError("mode_order='opt' is resolved by resolve_schedule "
                         "(the DP search needs solver costs and the memory "
                         "cap), not by resolve_mode_order")
    if mode_order == "shrink":
        return sorted(range(n), key=lambda m: ranks[m] / shape[m])
    order = [int(m) for m in mode_order]
    if sorted(order) != list(range(n)):
        raise ValueError(f"mode_order {order} must be a permutation of 0..{n - 1}")
    return order


def validate_ranks(shape: Sequence[int], ranks: Sequence[int]) -> tuple[int, ...]:
    ranks = tuple(int(r) for r in ranks)
    if len(ranks) != len(shape):
        raise ValueError(f"ranks {ranks} do not match tensor order {len(shape)}")
    for m, (i, r) in enumerate(zip(shape, ranks)):
        if not (1 <= r <= i):
            raise ValueError(f"rank {r} invalid for mode {m} (dim {i})")
    return ranks


def _resolve_methods(methods, n_modes: int):
    """Normalize ``methods`` to either None (= use selector) or a per-mode list."""
    if methods == "auto":
        return None
    if isinstance(methods, str):
        methods = [methods] * n_modes
    else:
        methods = list(methods)
        if len(methods) != n_modes:
            raise ValueError(f"need {n_modes} per-mode methods, got {len(methods)}")
    for m in methods:
        if m not in SOLVERS:
            raise ValueError(f"unknown solver {m!r}")
    return methods


def _step_cost(method: str, i_n: int, r_n: int, j_n: int,
               als_iters: int) -> float:
    if method == "eig":
        return eig_flops(i_n, r_n, j_n)
    if method == "als":
        return als_flops(i_n, r_n, j_n, als_iters)
    if method == "rand":
        return rand_flops(i_n, r_n, j_n)
    return svd_flops(i_n, r_n, j_n)


def _solver_scratch_bytes(method: str, i_n: int, r_n: int, j_n: int,
                          itemsize: int, n_shards: int = 1) -> int:
    """Modeled solver scratch only (no I/O tensors): EIG's I_n×I_n Gram,
    ALS's iterates (+ fp32 input cast for sub-fp32 dtypes), SVD's
    explicit unfolding plus its left singular block.  Scratch lives in the
    *accumulation* dtype; sharded parts (ALS's R-tensor and cast, which
    stay with the input) divide by ``n_shards`` while replicated scratch
    (EIG's psum'd Gram, ALS's basis Q, its L block and QR's R_n×R_n
    triangle) does not."""
    accum = max(itemsize, 4)   # bf16/fp16 accumulate in fp32; fp64 stays 8
    if method == "eig":
        return i_n * i_n * accum               # replicated psum'd Gram
    if method == "als":
        scratch = (2 * i_n * r_n + r_n * r_n) * accum \
            + r_n * j_n * accum // n_shards   # R-tensor stays sharded
        if accum != itemsize:
            scratch += i_n * j_n * accum // n_shards  # the fp32 input cast
        return scratch
    if method == "rand":
        # Gaussian test tensor Ω (ℓ·J) + range sample / Q (I·ℓ) + the ℓ-wide
        # projected tensor b (ℓ·J) + the ℓ×ℓ sketched Gram; plus the fp32
        # input cast for sub-fp32 dtypes (like ALS).  Replicated by design
        # (the sketch runs before any reshard; see _make_step).
        ell = min(i_n, r_n + DEFAULT_OVERSAMPLE)
        scratch = (2 * ell * j_n + i_n * ell + ell * ell) * accum
        if accum != itemsize:
            scratch += i_n * j_n * accum
        return scratch
    # svd materializes the unfolding and U, replicated by design
    return (i_n * j_n + i_n * min(i_n, j_n)) * accum


def _step_peak_bytes(method: str, i_n: int, r_n: int, j_n: int,
                     itemsize: int, n_shards: int = 1) -> int:
    """Modeled peak working set: input + output tensors plus solver scratch
    (see :func:`_solver_scratch_bytes`).

    I/O tensors live in the compute dtype (``itemsize``); with
    ``n_shards > 1`` the figure is PER DEVICE: the I/O slabs divide by the
    shard count, replicated scratch does not — the paper's GPU OOM regime
    is exactly where this distinction decides whether a mode fits.
    """
    io = (i_n * j_n + r_n * j_n) * itemsize // n_shards
    return int(io + _solver_scratch_bytes(method, i_n, r_n, j_n, itemsize,
                                          n_shards))


def _group_peak_bytes(entries, in_elems: int, out_elems: int,
                      itemsize: int, n_shards: int = 1) -> int:
    """Modeled per-device peak of one mode-parallel group: the SHARED
    un-shrunk input slab (every member's Gram reads the same tensor, so it
    is charged once), the fused multi-TTM's fully-truncated output slab,
    plus every member's solver scratch CONCURRENTLY (the latency win of
    running G Grams at once is paid for in G live scratches — the memory
    coupling that lets a cap force a group to split).

    ``entries`` is a sequence of ``(method, i_n, r_n, j_n)`` at the group's
    entry shape.  For a singleton group this reduces exactly to
    :func:`_step_peak_bytes` (in = I_n·J_n, out = R_n·J_n, one scratch).
    """
    io = (in_elems + out_elems) * itemsize // n_shards
    scratch = sum(_solver_scratch_bytes(meth, i_n, r_n, j_n, itemsize,
                                        n_shards)
                  for meth, i_n, r_n, j_n in entries)
    return int(io + scratch)


def iter_groups(steps):
    """Partition a schedule into execution groups: consecutive steps sharing
    a non-None ``group`` id run as ONE mode-parallel group (all factors from
    the shared un-shrunk input, one fused multi-TTM truncation); ``None``
    steps are sequential singletons.  Yields lists of :class:`ModeStep`."""
    batch: list = []
    for s in steps:
        if batch and s.group is not None and s.group == batch[0].group:
            batch.append(s)
            continue
        if batch:
            yield batch
        batch = [s]
    if batch:
        yield batch


def _make_step(mode: int, method, selector, i_n: int, r_n: int, j_n: int,
               als_iters: int, itemsize: int, backend: str,
               n_shards: int = 1, shard_mode: int | None = None,
               cost_model=None, group: int | None = None,
               peak_override: int | None = None) -> ModeStep:
    m = selector(i_n=i_n, r_n=r_n, j_n=j_n) if method is None else method
    if m not in SOLVERS:
        raise ValueError(f"unknown solver {m!r}")
    if not get_backend(backend).supports_solver(m):
        raise ValueError(
            f"backend {backend!r} does not support solver {m!r} "
            f"(capability metadata lists {get_backend(backend).solvers}); "
            "pin a supported method or pick another impl")
    if m in ("svd", "rand"):
        # SVD matricizes; RAND's sketch/QR pipeline has no collective form
        # yet (distributed.solve_step_sharded handles eig/als only) — both
        # run replicated in sharded schedules
        shard_mode = None
    eff_shards = n_shards if shard_mode is not None else 1
    scale = get_backend(backend).cost_scale
    # a calibrated cost model (repro.tune.calibrate) predicts wall-clock per
    # step; its scales already absorb the backend it was fitted on, so the
    # registry cost_scale hint is NOT applied on top
    predicted_s = cost_model.predict_seconds(m, i_n, r_n, j_n, als_iters) \
        if cost_model is not None and cost_model.calibrated else 0.0
    peak = _step_peak_bytes(m, i_n, r_n, j_n, itemsize, eff_shards) \
        if peak_override is None else peak_override
    return ModeStep(mode=mode, method=m, i_n=i_n, r_n=r_n, j_n=j_n,
                    flops=scale * _step_cost(m, i_n, r_n, j_n, als_iters),
                    peak_bytes=peak,
                    backend=backend, shard_mode=shard_mode,
                    n_shards=eff_shards, predicted_s=predicted_s,
                    group=group)


def _make_group_steps(g, gid: int, cur, ranks, methods_g, selector,
                      als_iters: int, itemsize: int, backend: str,
                      n_shards: int, cost_model) -> list[ModeStep]:
    """Emit the ModeSteps of one mode-parallel group: every member is sized
    at the GROUP-ENTRY shape (``j_n`` keeps the other members un-shrunk —
    the FLOPs premium of parallel execution), one shard mode serves the
    whole group (chosen OUTSIDE it, so every member's Gram keeps the shard
    axis inside its contraction dims; ``None`` = replicated when the group
    covers every shardable mode), and the GROUP's modeled peak — shared
    input slab + all members' concurrent scratch — is stamped on each
    member."""
    j_base = math.prod(cur)
    if n_shards > 1:
        from .distributed import pick_shard_mode_group
        shard = pick_shard_mode_group(tuple(cur), g, n_shards)
    else:
        shard = None
    eff = n_shards if shard is not None else 1
    resolved = []
    for m, meth in zip(g, methods_g):
        i_n, r_n = cur[m], ranks[m]
        j_n = j_base // i_n
        meth = selector(i_n=i_n, r_n=r_n, j_n=j_n) if meth is None else meth
        if meth in ("svd", "rand"):
            raise ValueError(
                f"mode {m} resolved to {meth!r}, which runs replicated and "
                "cannot join a mode-parallel group; pin eig/als for grouped "
                f"modes (mode_parallel='auto' never groups {meth})")
        resolved.append((meth, i_n, r_n, j_n))
    out_elems = j_base
    for m in g:
        out_elems = out_elems // cur[m] * ranks[m]
    gpeak = _group_peak_bytes(resolved, j_base, out_elems, itemsize, eff)
    return [
        _make_step(m, meth, None, i_n, r_n, j_n, als_iters, itemsize,
                   backend, n_shards, shard, cost_model=cost_model,
                   group=gid, peak_override=gpeak)
        for m, (meth, i_n, r_n, j_n) in zip(g, resolved)]


def resolve_schedule(
    shape: Sequence[int],
    ranks: Sequence[int],
    *,
    variant: str = "sthosvd",
    methods="auto",
    mode_order=None,
    selector: Callable[..., str] | None = None,
    als_iters: int = DEFAULT_ALS_ITERS,
    hooi_iters: int = 3,
    itemsize: int = 4,
    backend: str = "matfree",
    n_shards: int = 1,
    cost_model=None,
    memory_cap_bytes: int | None = None,
    mode_parallel: str | int = "off",
) -> tuple[ModeStep, ...]:
    """Resolve the full per-mode solver schedule ahead of execution.

    Every (I_n, R_n, J_n) triple a runtime selector would have seen is
    derived from ``shape``/``ranks`` alone, so selection runs zero times at
    execute time.  A HOOI schedule starts with its st-HOSVD init sweep.

    ``itemsize`` is the byte width of the *compute* dtype (callers derive it
    from ``TuckerConfig.compute_dtype`` or the input dtype — never assume 4)
    and ``backend`` the resolved ops-backend name stamped on every step.

    ``n_shards > 1`` resolves the DISTRIBUTION schedule too (sharded/mesh
    backend, st-HOSVD only): each step freezes the shard mode the tensor
    lives on while that mode is solved — the largest remaining mode (other
    than the one being solved) that divides by the shard count, via
    :func:`repro.core.distributed.pick_shard_mode` — so reshard points are
    known ahead of execution and ``peak_bytes`` become per-device figures.

    ``cost_model`` (a :class:`repro.core.cost_model.CostModel`) annotates
    each step with its predicted wall-clock (``ModeStep.predicted_s``) when
    CALIBRATED (``repro.tune.calibrate``); the textbook model carries no
    seconds unit, so uncalibrated schedules record 0.0.  When a selector is
    auto-resolved here, its embedded cost model is used.

    ``mode_order="opt"`` (st-HOSVD and the HOOI init sweep) runs the exact
    subset DP of :mod:`repro.core.schedule_opt`, jointly choosing mode order
    AND per-step solver (respecting pinned ``methods``) to minimize the cost
    model's predicted total — seconds when calibrated, Eq. 4/5 FLOPs
    otherwise — subject to ``memory_cap_bytes``.

    ``memory_cap_bytes`` is a hard per-device ceiling on every step's
    modeled ``peak_bytes``: fixed-order schedules that exceed it (and
    ``"opt"`` searches that cannot fit under it) raise
    :class:`repro.core.schedule_opt.MemoryCapError` at plan time, naming
    the binding step — the paper's OOM regime fails before the first byte
    is allocated, and a tight cap can force the slower-but-smaller solver.

    ``mode_parallel`` (sharded st-HOSVD only) opens mode-PARALLEL groups:
    group members compute their Grams/iterates concurrently from the same
    un-shrunk tensor and truncate together in one fused multi-TTM — lower
    latency (fewer collective barriers, priced as the max over members) at
    more FLOPs (members see un-shrunk ``j_n``).  ``"off"`` (default) keeps
    the sequential shrink; an int G groups the leading G modes of the
    resolved order; ``"auto"`` lets the DP price sequential-vs-parallel per
    input — jointly with order/solver when ``mode_order="opt"``, as a
    grouping search along the fixed order otherwise.  Group peaks charge
    the shared input slab plus every member's concurrent scratch, so a
    tight ``memory_cap_bytes`` can force a group to split.  ``"auto"``
    degrades to sequential when ``n_shards <= 1`` (no concurrent mesh
    resources); an explicit int G > 1 there is an error.
    """
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; expected one of {VARIANTS}")
    get_backend(backend)   # concrete, registered backend only (never "auto")
    if n_shards > 1 and variant != "sthosvd":
        raise ValueError(f"sharded schedules support variant 'sthosvd' only, "
                         f"got {variant!r} (t-HOSVD/HOOI re-solve from the "
                         "full tensor; reshard scheduling assumes the "
                         "sequential shrink)")
    mp: str | int = mode_parallel
    if isinstance(mp, bool) or \
            not (mp in ("off", "auto") or isinstance(mp, int)):
        raise ValueError(f"mode_parallel {mode_parallel!r} must be 'off', "
                         "'auto', or an int max group size")
    if isinstance(mp, int):
        if mp < 1:
            raise ValueError(f"mode_parallel={mp} must be >= 1")
        if mp == 1:
            mp = "off"   # a group of one IS the sequential step
    if mp != "off":
        if variant != "sthosvd":
            raise ValueError("mode_parallel applies to the sequential "
                             "st-HOSVD sweep only; leave it 'off' for "
                             f"variant {variant!r}")
        if n_shards <= 1:
            if mp == "auto":
                mp = "off"   # single device: no concurrent mode resources,
                             # sequential shrinking always wins the latency race
            else:
                raise ValueError(
                    f"mode_parallel={mp} needs a sharded schedule "
                    "(n_shards > 1): single-device execution has no "
                    "concurrent mesh resources to assign mode Grams to")
    shape = tuple(int(s) for s in shape)
    ranks = validate_ranks(shape, ranks)
    n = len(shape)
    fixed = _resolve_methods(methods, n)
    if fixed is None and selector is None:
        from .selector import default_selector
        selector = default_selector(backend=backend)
    if cost_model is None:
        # a trained selector carries the calibration fitted from the same
        # records; TimedSelector exposes the wrapped selector's cost_model
        cost_model = getattr(selector, "cost_model", None)

    def method_for(mode):
        return None if fixed is None else fixed[mode]

    def _capped(steps_t: tuple[ModeStep, ...]) -> tuple[ModeStep, ...]:
        # hard plan-time cap: "opt" schedules were searched under it, but the
        # check runs uniformly so fixed orders (and HOOI refinements, which
        # the DP does not reorder) fail loudly too
        if memory_cap_bytes is not None:
            from .schedule_opt import validate_schedule_cap
            validate_schedule_cap(steps_t, memory_cap_bytes)
        return steps_t

    steps: list[ModeStep] = []
    if variant == "thosvd":
        if mode_order is not None:
            raise ValueError("mode_order is meaningless for thosvd (factors "
                             "are computed independently from the original "
                             "tensor); leave it None")
        size = math.prod(shape)
        for mode in range(n):
            i_n, r_n = shape[mode], ranks[mode]
            steps.append(_make_step(mode, method_for(mode), selector,
                                    i_n, r_n, size // i_n, als_iters,
                                    itemsize, backend,
                                    cost_model=cost_model))
        return _capped(tuple(steps))

    # st-HOSVD sweep (also HOOI's init): the tensor shrinks between steps
    # (or between GROUPS when mode_parallel opens one)
    if n_shards > 1:
        from .distributed import pick_shard_mode
    flat_methods: list | None
    if mp == "auto":
        # the planner prices sequential-vs-parallel per input: joint
        # subset DP when the order is searched too, grouping search
        # along the fixed order otherwise
        from .schedule_opt import optimize_grouping, optimize_schedule
        if mode_order == "opt":
            search = optimize_schedule(
                shape, ranks, methods=fixed, als_iters=als_iters,
                itemsize=itemsize, n_shards=n_shards,
                cost_model=cost_model,
                memory_cap_bytes=memory_cap_bytes, max_group=n)
        else:
            search = optimize_grouping(
                shape, ranks,
                tuple(resolve_mode_order(shape, ranks, mode_order)),
                methods=fixed, als_iters=als_iters, itemsize=itemsize,
                n_shards=n_shards, cost_model=cost_model,
                memory_cap_bytes=memory_cap_bytes)
        groups = list(search.groups)
        flat_methods = list(search.methods)
    else:
        if mode_order == "opt":
            from .schedule_opt import optimize_schedule
            search = optimize_schedule(
                shape, ranks, methods=fixed, als_iters=als_iters,
                itemsize=itemsize, n_shards=n_shards,
                cost_model=cost_model,
                memory_cap_bytes=memory_cap_bytes)
            order, flat_methods = list(search.order), list(search.methods)
        else:
            order = resolve_mode_order(shape, ranks, mode_order)
            flat_methods = None
        if mp == "off":
            groups = [(m,) for m in order]
        else:   # int G >= 2: fixed strategy — leading group, rest sequential
            g_lead = min(int(mp), n)
            groups = [tuple(order[:g_lead])] + [(m,) for m in order[g_lead:]]
    cur = list(shape)
    pos = 0
    gid = 0
    for g in groups:
        if len(g) == 1:
            mode = g[0]
            i_n, r_n = cur[mode], ranks[mode]
            j_n = math.prod(cur) // i_n
            shard = pick_shard_mode(tuple(cur), mode, n_shards) \
                if n_shards > 1 else None
            method = flat_methods[pos] if flat_methods is not None \
                else method_for(mode)
            steps.append(_make_step(mode, method, selector,
                                    i_n, r_n, j_n, als_iters, itemsize,
                                    backend, n_shards, shard,
                                    cost_model=cost_model))
            cur[mode] = r_n
        else:
            meths_g = [flat_methods[pos + i] if flat_methods is not None
                       else method_for(m) for i, m in enumerate(g)]
            steps.extend(_make_group_steps(
                g, gid, cur, ranks, meths_g, selector, als_iters,
                itemsize, backend, n_shards, cost_model))
            for m in g:
                cur[m] = ranks[m]
            gid += 1
        pos += len(g)
    if variant == "sthosvd":
        return _capped(tuple(steps))

    # HOOI refinement sweeps: mode n sees x projected on all OTHER factors,
    # i.e. shape (R_0 .. I_n .. R_{N-1}) — static, so resolvable up front.
    rank_prod = math.prod(ranks)
    for _ in range(hooi_iters):
        for mode in range(n):
            i_n, r_n = shape[mode], ranks[mode]
            j_n = rank_prod // r_n
            steps.append(_make_step(mode, method_for(mode), selector,
                                    i_n, r_n, j_n, als_iters, itemsize,
                                    backend, cost_model=cost_model))
    return _capped(tuple(steps))


# ---------------------------------------------------------------------------
# Single solver dispatch + the timed solve of the recorded path
# ---------------------------------------------------------------------------

def solve_step(y: jax.Array, step: ModeStep, *, als_iters: int = DEFAULT_ALS_ITERS,
               oversample: int = DEFAULT_OVERSAMPLE,
               power_iters: int = DEFAULT_POWER_ITERS,
               impl: str | None = None):
    """THE solver dispatch point: every variant's mode solve funnels here.

    ``impl`` overrides the step's recorded ops backend; by default each step
    runs on the backend frozen into it at schedule-resolution time.
    ``oversample``/``power_iters`` only affect ``"rand"`` steps (sketch
    width ℓ = R_n + oversample and subspace-iteration count).
    """
    impl = step.backend if impl is None else impl
    if step.method == ALS:
        return SOLVERS[ALS](y, step.mode, step.r_n, num_iters=als_iters, impl=impl)
    if step.method == RAND:
        return SOLVERS[RAND](y, step.mode, step.r_n, oversample=oversample,
                             power_iters=power_iters, impl=impl)
    return SOLVERS[step.method](y, step.mode, step.r_n, impl=impl)


def _solve_attrs(batch: Sequence[ModeStep], backend: str,
                 platform: str) -> dict:
    """Attributes of the ``solve`` span around one step, or around a
    mode-parallel group as a whole (its modes, their shared solver or
    ``"mixed"``, and the sum of their predictions)."""
    s = batch[0]
    attrs = dict(solver=s.method, backend=backend, platform=platform)
    if backend == "sharded":
        attrs.update(n_shards=s.n_shards, group=s.group)
    if len(batch) == 1:
        return dict(attrs, mode=s.mode, rank=s.r_n, i_n=s.i_n, j_n=s.j_n,
                    predicted_s=s.predicted_s)
    methods = {b.method for b in batch}
    return dict(attrs, modes=[b.mode for b in batch],
                solver=methods.pop() if len(methods) == 1 else "mixed",
                predicted_s=sum(b.predicted_s for b in batch))


class StepTimer:
    """The timed solve a sweep runs with when it runs eagerly.

    Passed as a sweep's ``solve`` (``solve=timer`` to the single-device
    sweeps, ``solve=timer.sharded`` to
    :func:`repro.core.distributed.sweep_sharded`), it turns the unjitted
    sweep into the recorded path: each step, or each mode-parallel group as
    one unit, runs inside a ``solve`` span with the ``solve`` chaos seam
    and the ``solve_out`` poison, is waited on, has its factors checked
    for non-finite values (a :class:`NumericalError` naming the step), and
    feeds the drift monitor.  ``seconds`` gets one entry per step, a
    group's wall-clock split evenly over its members, so it stays
    index-aligned with the schedule.
    """

    def __init__(self):
        self.seconds: list[float] = []
        self.platform = jax.default_backend()

    def __call__(self, y: jax.Array, step: ModeStep, *,
                 impl: str | None = None, **kw) -> SolveResult:
        """:func:`solve_step`, timed."""
        def run():
            res = solve_step(y, step, impl=impl, **kw)
            return {step.mode: res.u}, res.y_new

        factors, y_new = self._timed([step], impl or step.backend, run)
        return SolveResult(factors[step.mode], y_new)

    def sharded(self, y: jax.Array, batch, mesh, axis: str, *,
                als_iters: int):
        """:func:`repro.core.distributed.solve_batch_sharded`, timed."""
        from .distributed import solve_batch_sharded
        return self._timed(batch, "sharded", lambda: solve_batch_sharded(
            y, batch, mesh, axis, als_iters=als_iters))

    def _timed(self, batch, backend: str, run):
        with _obs.span("solve", **_solve_attrs(batch, backend, self.platform)):
            t0 = time.perf_counter()
            for s in batch:
                _chaos.fire("solve", mode=s.mode, method=s.method)
            factors, y = run()
            if _chaos.active():
                factors = {m: u * float("nan")
                           if _chaos.poison("solve_out", mode=m) else u
                           for m, u in factors.items()}
            jax.block_until_ready((factors, y))
            dt = time.perf_counter() - t0
        for s in batch:
            # a breakdown that slipped past the in-solver guards (e.g. a
            # non-finite Gram) shows up here as NaN factors — surface it
            # as a classified error naming the step, not as silent poison
            if not bool(jax.numpy.all(jax.numpy.isfinite(factors[s.mode]))):
                raise NumericalError(
                    f"{s.method} solve on mode {s.mode} produced a "
                    "non-finite factor (numerical breakdown)")
        share = dt / len(batch)
        for s in batch:
            _drift.MONITOR.observe(platform=self.platform, backend=backend,
                                   solver=s.method,
                                   predicted_s=s.predicted_s,
                                   actual_s=share, source="execute")
        self.seconds.extend([share] * len(batch))
        return factors, y


# ---------------------------------------------------------------------------
# Whole-sweep pure functions (compiled as ONE program by api.TuckerPlan)
# ---------------------------------------------------------------------------

def step_scope(step: ModeStep):
    """The named scope ``mode{m}.{method}`` a compiled sweep traces one
    schedule step under, so the step's ops carry it in a profile."""
    return jax.named_scope(f"mode{step.mode}.{step.method}")


def sweep_sthosvd(x, steps: Sequence[ModeStep], *, als_iters: int,
                  oversample: int = DEFAULT_OVERSAMPLE,
                  power_iters: int = DEFAULT_POWER_ITERS,
                  impl: str | None = None, solve=solve_step):
    y = x
    factors: dict[int, jax.Array] = {}
    for step in steps:
        with step_scope(step):
            res = solve(y, step, als_iters=als_iters, oversample=oversample,
                        power_iters=power_iters, impl=impl)
        factors[step.mode] = res.u
        y = res.y_new
    return y, [factors[m] for m in range(x.ndim)]


def sweep_thosvd(x, steps: Sequence[ModeStep], *, als_iters: int,
                 oversample: int = DEFAULT_OVERSAMPLE,
                 power_iters: int = DEFAULT_POWER_ITERS,
                 impl: str | None = None, solve=solve_step):
    factors = []
    for step in steps:
        with step_scope(step):
            factors.append(solve(
                x, step, als_iters=als_iters, oversample=oversample,
                power_iters=power_iters, impl=impl).u)
    core = x
    for mode, u in enumerate(factors):
        core = T.ttm(core, u.T, mode)
    return core, factors


def sweep_hooi(x, steps: Sequence[ModeStep], *, als_iters: int,
               oversample: int = DEFAULT_OVERSAMPLE,
               power_iters: int = DEFAULT_POWER_ITERS,
               impl: str | None = None, solve=solve_step):
    """HOOI with its st-HOSVD init inlined: the first ``x.ndim`` steps are
    the init sweep (sequential shrink), the rest are refinement solves on x
    projected over every factor but the step's mode."""
    kw = dict(als_iters=als_iters, oversample=oversample,
              power_iters=power_iters, impl=impl)
    _, factors = sweep_sthosvd(x, steps[:x.ndim], solve=solve, **kw)
    for step in steps[x.ndim:]:
        with step_scope(step):
            y = x
            for m, u in enumerate(factors):
                if m != step.mode:
                    y = T.ttm(y, u.T, m)
            factors[step.mode] = solve(y, step, **kw).u
    core = x
    for mode, u in enumerate(factors):
        core = T.ttm(core, u.T, mode)
    return core, factors


#: THE definition of each variant: the compiled program
#: (:func:`repro.core.api._make_sweep`) and the recorded path
#: (``TuckerPlan.execute(record=True)``, with a :class:`StepTimer` as the
#: ``solve``) both run the function this table names.
SWEEPS = {"sthosvd": sweep_sthosvd, "thosvd": sweep_thosvd,
          "hooi": sweep_hooi}
