"""Plan/execute front door for a-Tucker: ``TuckerConfig`` → ``TuckerPlan``.

The legacy entry points (`sthosvd` & friends) re-run the adaptive selector
and re-dispatch solvers inside every call.  Following the plan/execute split
of randomized-Tucker systems that precompute their sketch/solve schedules,
this module moves ALL input-adaptive decisions to a one-time ``plan`` step:

    cfg  = TuckerConfig(ranks=(10, 10, 5), methods="auto")
    p    = plan(x.shape, x.dtype, cfg)     # selector runs here, never again
    res  = p.execute(x)                    # ONE cached jitted program
    ress = p.execute_batch(xs)             # same program, vmapped over axis 0

Because the per-mode solver schedule and mode order are frozen in the plan,
the entire sweep traces as a single XLA program, cached process-wide by
``(shape, dtype, schedule+backend, variant, als_iters, compute_dtype)`` — so
repeated executes on same-shaped inputs cost zero recompiles and zero
selector invocations.  Plans are JSON-serializable (``save``/``load``,
mirroring ``Selector.save``) so a schedule tuned on one box can ship to
another.

Rank-ADAPTIVE plans trade fixed ranks for an error target:

    cfg = TuckerConfig(error_target=0.05)        # ||X - X̂|| ≤ 0.05·||X||
    p   = plan(x.shape, x.dtype, cfg)            # freezes a rank POLICY
    res = p.execute(x)                           # sketches ranks, refines
    res.tucker.ranks, res.error_bound            # what the policy chose

The plan carries per-step candidate grids and equi-partitioned HOSVD
budgets instead of ranks; execution reads each mode's rank off a
randomized sketch (matricization-free, the same TTM/TTT/Gram kernels) and
either ships the sketch factors directly (``methods="rand"``) or refines
at the chosen ranks through the ordinary fixed-rank compiled path.
"""

from __future__ import annotations

import json
import math
import os
import sys
from dataclasses import dataclass, replace
from functools import lru_cache
from pathlib import Path
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from .. import chaos as _chaos
from ..obs import drift as _drift
from ..obs import metrics as _metrics
from ..obs import trace as _obs
from .backend import backend_names, get_backend, resolve_backend
from .errors import (CancelledError, DeadlineError, InputError,
                     NumericalError, ResourceError, TuckerError,
                     check_finite, check_result_finite, classify_exception)
from .plan import (
    SWEEPS,
    VARIANTS,
    ModeStep,
    StepTimer,
    TimedSelector,
    resolve_schedule,
    solve_step,
)
from .solvers import DEFAULT_ALS_ITERS, DEFAULT_OVERSAMPLE, DEFAULT_POWER_ITERS
from .sthosvd import ModeTrace, SthosvdResult, TuckerTensor

PLAN_FORMAT_VERSION = 1


def mesh_spec(mesh: Mesh | None) -> dict | None:
    """JSON-serializable description of a mesh: axis names + per-axis sizes.
    Device identities are deliberately NOT serialized — a plan tuned on one
    box re-materializes its mesh from the local devices on another."""
    if mesh is None:
        return None
    return {"axis_names": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names]}


def mesh_from_spec(spec: dict | None) -> Mesh | None:
    """Rebuild a mesh from :func:`mesh_spec` output against the LOCAL
    devices.  Returns None when the spec is None or the local process has
    too few devices — the plan then loads fine for inspection but
    ``execute`` raises until a real mesh is available."""
    if spec is None:
        return None
    shape = tuple(int(s) for s in spec["shape"])
    if math.prod(shape) > len(jax.devices()):
        return None
    return jax.make_mesh(shape, tuple(spec["axis_names"]))


@dataclass(frozen=True)
class TuckerConfig:
    """Frozen description of a Tucker decomposition job (the *what*).

    ``plan()`` turns it plus a concrete (shape, dtype) into a ``TuckerPlan``
    (the *how*): per-mode solvers resolved, costs estimated, sweep compiled.

    compute_dtype is the precision policy: inputs are cast to it before the
    sweep (e.g. "float32" to decompose bf16 weights at full precision); the
    default ``None`` keeps the input dtype.

    ``impl`` names an ops backend from :mod:`repro.core.backend` (``matfree``
    | ``explicit`` | ``pallas`` | ``sharded`` | any custom-registered name)
    or ``"auto"`` to let ``plan()`` pick the best backend for the current
    platform and compute dtype; the resolved choice is frozen into the
    plan's schedule.

    ``mesh`` attaches a ``jax.sharding.Mesh`` for multi-device execution:
    ``impl="sharded"`` requires one, and ``impl="auto"`` resolves to the
    sharded backend whenever one is present.  ``shard_axis`` names the mesh
    axis the tensor is sharded over (default: the mesh's first axis).  The
    mesh serializes as its SPEC (axis names + sizes, see :func:`mesh_spec`)
    — device handles never enter plan JSON.

    ``mode_order`` orders the st-HOSVD sweep: ``None`` (the paper's 1..N),
    an explicit permutation, ``"shrink"`` (greedy compression-ratio
    heuristic), or ``"opt"`` — the exact subset-DP schedule search
    (:mod:`repro.core.schedule_opt`) that jointly picks order AND per-step
    solver against the cost model's predicted total, under
    ``memory_cap_bytes`` when set.

    ``memory_cap_bytes`` is a hard per-device ceiling on every step's
    modeled peak working set: plans that cannot fit raise
    :class:`~repro.core.schedule_opt.MemoryCapError` at plan time naming
    the binding step (the paper's GPU OOM regime, decided before any
    allocation).

    ``donate_input`` controls whether the compiled sweep donates its input
    buffer to XLA (``jax.jit(donate_argnums=0)``) so a sweep stops holding
    a dead copy of X.  ``None`` (auto, the default) donates only the device
    copy ``execute`` itself materialized from a host array — a caller's
    jax array is never invalidated silently; ``True`` always donates (the
    input is CONSUMED — ``x`` is unusable after ``execute(x)``); ``False``
    disables donation by default (an explicit per-call
    ``execute(x, donate=True)`` still wins — the caller owns the buffer).
    Donation is automatically disabled where unsupported
    (sharded shard_map sweeps, interpret-mode backends, platforms without
    buffer aliasing) and globally via the ``ATUCKER_NO_DONATE`` env var.

    ``mode_parallel`` opts sharded st-HOSVD sweeps into MODE-PARALLEL
    groups: group members compute their Grams concurrently from the same
    un-shrunk tensor (one mesh barrier for the whole group) and truncate in
    one fused multi-TTM — lower latency, more FLOPs.  ``"off"`` (default)
    keeps the sequential shrinking sweep; an int ``G ≥ 2`` forces the first
    G modes of the resolved order into one group; ``"auto"`` lets the
    schedule DP price sequential vs every grouping per input (latency =
    max over group members, memory = shared input + concurrent scratches,
    under ``memory_cap_bytes``) and silently stays sequential on
    single-device plans.

    ``error_target`` switches the plan RANK-ADAPTIVE (st-HOSVD only): pass a
    target relative reconstruction error ε ∈ (0, 1) and ``ranks`` becomes
    optional — the plan carries a rank POLICY instead of fixed ranks, and
    execution reads each mode's rank off a randomized sketch
    (:func:`repro.core.solvers.rand_sketch`): the smallest candidate whose
    measured discarded energy fits the mode's equi-partitioned share
    ``τ_n² = ε²·||X||²/N`` of the HOSVD bound ``||X − X̂||² ≤ Σ_n τ_n²``.
    ``ranks``, when also given, caps the per-mode rank; ``rank_grid``
    restricts the candidates — a flat int tuple is one shared ascending
    grid for every mode, a tuple of tuples is per-mode (default: every rank
    up to the cap).  ``methods`` then names the solver that REFINES the
    decomposition at the chosen ranks through the ordinary fixed-rank
    compiled path (``"auto"``/``"eig"``/``"als"`` …); ``methods="rand"``
    skips refinement and ships the sketch's own factors — the fastest path,
    still within ε.  ``oversample``/``power_iters`` tune the sketch
    (ℓ = r + oversample columns, subspace-iteration count).

    ``SthosvdResult.error_bound`` then reports the certified bound
    ``sqrt(Σ_n tail_n)/||X||`` measured from the executed sketch.
    """
    ranks: tuple[int, ...] | None = None
    variant: str = "sthosvd"
    methods: str | tuple[str, ...] = "auto"
    mode_order: tuple[int, ...] | str | None = None
    impl: str = "matfree"
    als_iters: int = DEFAULT_ALS_ITERS
    hooi_iters: int = 3
    compute_dtype: str | None = None
    mesh: Mesh | None = None
    shard_axis: str | None = None
    memory_cap_bytes: int | None = None
    donate_input: bool | None = None
    mode_parallel: str | int = "off"
    error_target: float | None = None
    rank_grid: tuple | None = None
    oversample: int = DEFAULT_OVERSAMPLE
    power_iters: int = DEFAULT_POWER_ITERS

    def __post_init__(self):
        if self.ranks is not None:
            object.__setattr__(self, "ranks",
                               tuple(int(r) for r in self.ranks))
        elif self.error_target is None:
            raise ValueError("TuckerConfig needs ranks=... (fixed-rank) or "
                             "error_target=... (rank-adaptive)")
        if self.error_target is not None:
            object.__setattr__(self, "error_target", float(self.error_target))
            if not 0.0 < self.error_target < 1.0:
                raise ValueError(f"error_target={self.error_target} must be "
                                 "a relative error in (0, 1)")
            if self.variant != "sthosvd":
                raise ValueError("error_target (rank-adaptive planning) "
                                 "needs the sequential-shrink error "
                                 "accounting of variant='sthosvd', got "
                                 f"{self.variant!r}")
            if self.mode_parallel != "off":
                raise ValueError("rank-adaptive plans are sequential (the "
                                 "per-mode budget check threads the shrink); "
                                 "mode_parallel must stay 'off'")
            if self.mesh is not None or self.impl == "sharded":
                raise ValueError("rank-adaptive plans run replicated (the "
                                 "sketch has no collective path); drop the "
                                 "mesh / sharded impl, or resolve ranks "
                                 "first and plan the fixed-rank sharded "
                                 "sweep at the result")
        if self.rank_grid is not None:
            if self.error_target is None:
                raise ValueError("rank_grid is part of the rank-adaptive "
                                 "policy; set error_target=... too (for "
                                 "fixed ranks pass ranks=...)")
            rg = tuple(self.rank_grid)
            if all(isinstance(g, int) for g in rg):
                object.__setattr__(self, "rank_grid",
                                   tuple(int(g) for g in rg))
            else:
                object.__setattr__(
                    self, "rank_grid",
                    tuple(tuple(int(r) for r in g) for g in rg))
            if not rg:
                raise ValueError("rank_grid must not be empty")
        if self.oversample < 0 or self.power_iters < 0:
            raise ValueError("oversample and power_iters must be >= 0")
        if not isinstance(self.methods, str):
            object.__setattr__(self, "methods", tuple(self.methods))
        if isinstance(self.mode_order, (list, tuple)):
            object.__setattr__(self, "mode_order",
                               tuple(int(m) for m in self.mode_order))
        if isinstance(self.mode_order, str) and \
                self.mode_order not in ("shrink", "opt"):
            raise ValueError(f"mode_order {self.mode_order!r} must be a "
                             "permutation, 'shrink', 'opt', or None")
        if self.memory_cap_bytes is not None:
            object.__setattr__(self, "memory_cap_bytes",
                               int(self.memory_cap_bytes))
            if self.memory_cap_bytes <= 0:
                raise ValueError("memory_cap_bytes must be a positive byte "
                                 "count (None = uncapped)")
        if self.variant not in VARIANTS:
            raise ValueError(f"unknown variant {self.variant!r}; "
                             f"expected one of {VARIANTS}")
        if self.impl != "auto":
            b = get_backend(self.impl)   # ValueError on unregistered names
            # a mesh on a single-device backend would be silently ignored —
            # the OOM-regime user who attached it deserves a loud error
            if self.mesh is not None and not b.requires_mesh:
                raise ValueError(
                    f"config carries a mesh but impl={self.impl!r} executes "
                    "on a single device; pass impl='sharded' (or 'auto', "
                    "which resolves to it when a mesh is present) or drop "
                    "the mesh")
        if self.als_iters < 1 or self.hooi_iters < 0:
            raise ValueError("als_iters must be ≥1 and hooi_iters ≥0")
        mp = self.mode_parallel
        if isinstance(mp, bool) or \
                not (mp in ("off", "auto") or isinstance(mp, int)):
            raise ValueError(f"mode_parallel {mp!r} must be 'off', 'auto', "
                             "or an int max group size")
        if isinstance(mp, int) and mp < 1:
            raise ValueError(f"mode_parallel={mp} must be >= 1")
        if self.shard_axis is not None and self.mesh is not None and \
                self.shard_axis not in self.mesh.axis_names:
            raise ValueError(f"shard_axis {self.shard_axis!r} not in mesh "
                             f"axes {self.mesh.axis_names}")

    @property
    def resolved_shard_axis(self) -> str | None:
        """The mesh axis sharded executions split over (explicit
        ``shard_axis`` or the mesh's first axis); None without a mesh."""
        if self.mesh is None:
            return self.shard_axis
        return self.shard_axis or self.mesh.axis_names[0]

    @property
    def n_shards(self) -> int:
        """Device count along the shard axis (1 without a mesh)."""
        return int(self.mesh.shape[self.resolved_shard_axis]) \
            if self.mesh is not None else 1

    def to_dict(self) -> dict:
        d = {"ranks": None if self.ranks is None else list(self.ranks),
             "variant": self.variant,
             "methods": (self.methods if isinstance(self.methods, str)
                         else list(self.methods)),
             "mode_order": (list(self.mode_order)
                            if isinstance(self.mode_order, tuple)
                            else self.mode_order),
             "impl": self.impl, "als_iters": self.als_iters,
             "hooi_iters": self.hooi_iters,
             "compute_dtype": self.compute_dtype,
             "mesh": mesh_spec(self.mesh),
             "shard_axis": self.shard_axis,
             "memory_cap_bytes": self.memory_cap_bytes,
             "donate_input": self.donate_input,
             "mode_parallel": self.mode_parallel}
        # rank-policy keys ride only on adaptive configs, so fixed-rank
        # config JSON is byte-identical to what pre-rank-policy versions
        # wrote (and they can still load it)
        if self.error_target is not None:
            d["error_target"] = self.error_target
            d["rank_grid"] = (None if self.rank_grid is None else
                              [list(g) if isinstance(g, tuple) else g
                               for g in self.rank_grid])
            d["oversample"] = self.oversample
            d["power_iters"] = self.power_iters
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TuckerConfig":
        rg = d.get("rank_grid")
        if rg is not None:
            rg = tuple(tuple(g) if isinstance(g, list) else int(g)
                       for g in rg)
        ranks = d["ranks"]
        return cls(ranks=None if ranks is None else tuple(ranks),
                   variant=d.get("variant", "sthosvd"),
                   methods=(d["methods"] if isinstance(d["methods"], str)
                            else tuple(d["methods"])),
                   mode_order=(tuple(d["mode_order"])
                               if isinstance(d.get("mode_order"), list)
                               else d.get("mode_order")),
                   impl=d.get("impl", "matfree"),
                   als_iters=d.get("als_iters", DEFAULT_ALS_ITERS),
                   hooi_iters=d.get("hooi_iters", 3),
                   compute_dtype=d.get("compute_dtype"),
                   mesh=mesh_from_spec(d.get("mesh")),
                   shard_axis=d.get("shard_axis"),
                   memory_cap_bytes=d.get("memory_cap_bytes"),
                   donate_input=d.get("donate_input"),
                   mode_parallel=d.get("mode_parallel", "off"),
                   error_target=d.get("error_target"),
                   rank_grid=rg,
                   oversample=d.get("oversample", DEFAULT_OVERSAMPLE),
                   power_iters=d.get("power_iters", DEFAULT_POWER_ITERS))


# ---------------------------------------------------------------------------
# Input-buffer donation
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def donation_supported(platform: str) -> bool:
    """Whether XLA honours input-output buffer aliasing on ``platform``.

    Probed once per process per platform by compiling a tiny donated
    program ON that platform's first device and checking the input buffer
    was actually invalidated — runtimes without aliasing (older CPU
    backends) silently ignore ``donate_argnums`` with a warning, and a
    sweep "donated" there would keep the dead copy of X alive anyway.
    """
    import warnings
    dev = jax.devices(platform)[0]
    # fresh, unshared buffer committed to the probed platform
    x = jax.device_put(jnp.zeros((2,), jnp.float32) + 1.0, dev)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        jax.block_until_ready(
            jax.jit(lambda a: a * 2.0, donate_argnums=0)(x))
    return bool(x.is_deleted())


# ---------------------------------------------------------------------------
# Process-wide compiled-sweep cache
# ---------------------------------------------------------------------------

_SWEEP_CACHE: dict[tuple, Callable] = {}

#: builds = new jitted programs constructed; hits = cache reuses;
#: traces = times a sweep body actually traced (== XLA compilations).
CACHE_STATS = {"builds": 0, "hits": 0, "traces": 0}


def clear_sweep_cache() -> None:
    _SWEEP_CACHE.clear()
    CACHE_STATS.update(builds=0, hits=0, traces=0)


def _plan_sweep(p: "TuckerPlan", timer: StepTimer | None = None) -> Callable:
    """``p``'s sweep as a function of x: the variant's function from
    :data:`repro.core.plan.SWEEPS` (or the mesh sweep) bound to the plan's
    frozen schedule and options.  The compiled program traces it as is;
    the recorded path runs it eagerly with ``timer`` as its solve."""
    steps, cfg = p.schedule, p.config   # each step carries its ops backend
    if p.backend == "sharded":
        from .distributed import solve_batch_sharded, sweep_sharded
        if cfg.mesh is None:
            raise RuntimeError(
                "plan requires a mesh to execute its sharded schedule (the "
                "loading process has too few devices to rebuild the plan's "
                "mesh spec, or the config lost its mesh); re-plan with "
                "TuckerConfig(mesh=...) on a large enough host")
        solve = solve_batch_sharded if timer is None else timer.sharded
        return lambda x: sweep_sharded(
            x, steps, mesh=cfg.mesh, axis=cfg.resolved_shard_axis,
            als_iters=cfg.als_iters, solve=solve)
    run = SWEEPS[cfg.variant]
    solve = solve_step if timer is None else timer
    return lambda x: run(x, steps, als_iters=cfg.als_iters, solve=solve)


def _make_sweep(p: "TuckerPlan", batched: bool, donate: bool = False) -> Callable:
    cdtype = jnp.dtype(p.config.compute_dtype) if p.config.compute_dtype \
        else None
    run = _plan_sweep(p)

    def sweep(x):
        CACHE_STATS["traces"] += 1
        if cdtype is not None:
            x = x.astype(cdtype)
        return run(x)

    if p.backend == "sharded":
        # donation is guarded off for shard_map sweeps upstream
        # (_resolve_donate); never build an aliasing program here
        if batched:
            raise RuntimeError("sharded sweeps do not vmap; execute_batch "
                               "runs sharded plans item by item")
        return jax.jit(sweep)

    jitted = jax.jit(jax.vmap(sweep) if batched else sweep,
                     donate_argnums=(0,) if donate else ())
    if not donate:
        return jitted

    def donating(x):
        # donate_argnums lets XLA alias X into any shape-matching output;
        # a Tucker sweep's outputs (core + factors) rarely match, in which
        # case XLA ignores the donation (with a warning) and the dead copy
        # of X would survive the whole sweep — so release it explicitly
        # right after dispatch (the runtime holds its own reference while
        # the async execution still needs it).
        import warnings
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            out = jitted(x)
        if not x.is_deleted():
            x.delete()
        return out

    return donating


def _compile_probe(fn: Callable, p: "TuckerPlan", batched: bool) -> Callable:
    """Wrap a freshly built sweep so its FIRST invocation — the one that
    traces and XLA-compiles — is spanned as ``compile`` on the bus (the
    duration includes the first execution; jit offers no clean split
    without AOT lowering).  Later calls pass straight through."""
    state = {"first": True}

    def probed(x):
        if not state["first"]:
            return fn(x)
        state["first"] = False
        with _obs.span("compile", shape=list(p.shape), dtype=p.dtype,
                       backend=p.backend, variant=p.config.variant,
                       batched=batched, includes_first_run=True):
            return fn(x)

    return probed


# ---------------------------------------------------------------------------
# TuckerPlan
# ---------------------------------------------------------------------------

@dataclass
class TuckerPlan:
    """A frozen, executable solver schedule for one (shape, dtype, config).

    ``schedule`` lists every mode solve in execution order with the solver
    the selector (or explicit methods) chose and the modeled FLOPs / peak
    working-set bytes of that step.  ``execute`` runs the whole sweep as one
    cached jitted program; ``execute_batch`` vmaps it over a leading axis.
    """
    shape: tuple[int, ...]
    dtype: str
    config: TuckerConfig
    schedule: tuple[ModeStep, ...]
    select_seconds: float = 0.0     # one-time planning cost (selector calls)

    # -- introspection -------------------------------------------------------
    @property
    def is_adaptive(self) -> bool:
        """True when this plan carries a rank POLICY (``error_target``)
        instead of fixed ranks: steps are sized at their rank caps (the
        conservative figure for memory modeling) and ``execute`` reads the
        actual per-mode ranks off a randomized sketch of each input."""
        return self.config.error_target is not None

    @property
    def backend(self) -> str:
        """The resolved ops backend this plan's steps run on (``config.impl``
        may be ``"auto"``; this is what it resolved to at plan time)."""
        names = {s.backend for s in self.schedule}
        return self.schedule[0].backend if len(names) == 1 else "mixed"

    @property
    def methods(self) -> tuple[str, ...]:
        """Resolved solver per mode (first visit order, sorted by mode)."""
        first: dict[int, str] = {}
        for s in self.schedule:
            first.setdefault(s.mode, s.method)
        return tuple(first[m] for m in sorted(first))

    @property
    def total_flops(self) -> float:
        return sum(s.flops for s in self.schedule)

    @property
    def total_predicted_s(self) -> float:
        """Predicted sweep wall-clock: the sum of the per-step calibrated
        cost-model predictions (0.0 when no calibration was available at
        plan time — compare against summed ``ModeTrace.seconds``)."""
        return sum(s.predicted_s for s in self.schedule)

    @property
    def als_passes(self) -> float:
        """Full reads that the ALS steps make of their own inputs (per
        iteration one TTM and one TTT, then the closing projection), each
        weighted by that input's elements over the decomposition's input:
        the input traffic of the ALS solver, from the plan alone."""
        reads = 2 * self.config.als_iters + 1
        return sum(reads * s.i_n * s.j_n for s in self.schedule
                   if s.method == "als") / math.prod(self.shape)

    @property
    def input_bytes(self) -> int:
        """Per-device bytes of the caller's input buffer — the plan's
        STORAGE dtype, not the compute dtype (the cast happens inside the
        jit; the buffer an undonated sweep keeps alive is x as passed) —
        divided by the first step's shard count for sharded plans."""
        return math.prod(self.shape) * jnp.dtype(self.dtype).itemsize \
            // self.schedule[0].n_shards

    @property
    def donates(self) -> bool:
        """Whether this plan's compiled sweep donates its input under the
        resolved static policy (config / env / backend guards; the ``None``
        auto policy counts as donating — the recommended host-input path
        materializes its own device copy, which IS donated)."""
        return self._resolve_donate(created=True, override=None)

    @property
    def peak_bytes(self) -> int:
        """Modeled per-device peak across the sweep, donation-aware: an
        undonated st-HOSVD sweep keeps the caller's (dead after step 0)
        input copy alive through every later step, so those steps charge
        ``input_bytes`` on top of their own working set; a donated sweep
        returns that buffer to XLA and pays only the per-step peaks.

        A leading mode-parallel group counts as "step 0" here: every member
        reads the full-size input, which its group peak already charges, so
        the dead-copy surcharge starts after the whole group."""
        base = max(s.peak_bytes for s in self.schedule)
        if self.config.variant != "sthosvd" or self.donates or \
                len(self.schedule) == 1:
            # t-HOSVD/HOOI read X in (almost) every step — it is already
            # counted in their per-step io, donated or not
            return base
        from .plan import iter_groups
        k0 = len(next(iter_groups(self.schedule)))
        if k0 >= len(self.schedule):
            return base
        extra = self.input_bytes
        return max(max(s.peak_bytes for s in self.schedule[:k0]),
                   max(s.peak_bytes + extra for s in self.schedule[k0:]))

    def _resolve_donate(self, created: bool, override: bool | None) -> bool:
        """Donation decision for one execute call.  ``created`` = the device
        buffer was materialized by execute itself (host input), so donating
        it can never invalidate a caller-held array.  ``override`` is the
        per-call argument; an explicit ``True``/``False`` at the call site
        beats ``config.donate_input`` (the caller owns the buffer), while
        the env escape hatch and the backend/platform guards beat both."""
        if override is False:
            return False
        if os.environ.get("ATUCKER_NO_DONATE"):
            return False
        if self.backend == "sharded":
            return False   # shard_map sweep: donation aliases live shards
        try:
            b = get_backend(self.backend)
        except ValueError:   # hand-built plan mixing backends per step
            return False
        if not b.native_on(jax.default_backend()):
            return False   # interpret-mode fallback: never alias a buffer
                           # the interpreter may still read
        if not donation_supported(jax.default_backend()):
            return False
        if override:       # per-call donate=True: consume x as documented
            return True
        cfg = self.config
        if cfg.donate_input is not None:
            return bool(cfg.donate_input)
        return created     # auto: only the copy execute itself materialized

    def _cache_key(self, batched: bool, donate: bool = False) -> tuple:
        # keyed on the RESOLVED per-step backend, not config.impl: two plans
        # whose "auto" resolved identically share one compiled sweep; sharded
        # plans additionally key on the mesh + frozen shard modes (a program
        # compiled for one device set never serves another); donated and
        # undonated variants are distinct programs (aliasing is compiled in)
        return (self.shape, self.dtype,
                tuple((s.mode, s.method, s.r_n, s.backend, s.shard_mode,
                       s.group)
                      for s in self.schedule),
                self.config.variant, self.config.als_iters,
                self.config.compute_dtype, batched, donate,
                self.config.mesh, self.config.resolved_shard_axis)

    def _sweep(self, batched: bool, donate: bool = False) -> Callable:
        key = self._cache_key(batched, donate)
        fn = _SWEEP_CACHE.get(key)
        if fn is None:
            fn = _SWEEP_CACHE[key] = _compile_probe(
                _make_sweep(self, batched, donate), self, batched)
            CACHE_STATS["builds"] += 1
            _obs.event("cache", status="miss", shape=list(self.shape),
                       dtype=self.dtype, backend=self.backend,
                       variant=self.config.variant, batched=batched,
                       donate=donate)
        else:
            # hits are counted but not published: a per-execute "hit" event
            # costs real µs on the warm path and says nothing the execute
            # span + CACHE_STATS don't (misses are the informative events)
            CACHE_STATS["hits"] += 1
        return fn

    def _place_input(self, x: jax.Array) -> jax.Array:
        """Sharded plans: land the input on the mesh pre-sharded the way the
        first step expects, so the compiled sweep starts from the frozen
        layout instead of paying a replicate-then-reshard."""
        if self.backend != "sharded" or self.config.mesh is None:
            return x
        from jax.sharding import NamedSharding

        from .distributed import _spec_for
        spec = _spec_for(len(self.shape), self.schedule[0].shard_mode,
                         self.config.resolved_shard_axis)
        return jax.device_put(x, NamedSharding(self.config.mesh, spec))

    # -- execution -----------------------------------------------------------
    def execute(self, x: jax.Array, *, record: bool = False,
                donate: bool | None = None,
                validate: str | None = None) -> SthosvdResult:
        """Run the frozen schedule on ``x`` as one compiled program.

        ``record=True`` (or an active :func:`repro.tune.recording` context)
        runs the same sweep function eagerly, step by step, so every mode
        solve gets real wall-clock in its trace — the traces then feed the
        autotune measurement store (predicted-vs-actual per step, and free
        training records from production traffic).  Sharded plans record
        too: a mode-parallel group is timed as one unit, its wall-clock
        split evenly over its members.

        ``donate`` overrides ``config.donate_input`` for this call: ``True``
        donates ``x``'s buffer into the sweep (``x`` is CONSUMED — deleted
        after the call), ``False`` never donates, ``None`` follows the
        config policy (auto: donate only the device copy this call itself
        materialized from a host array).

        ``validate="finite"`` rejects NaN/Inf inputs up front with
        :class:`~repro.core.errors.InputError` naming the offending mode,
        and checks the sweep's outputs (raising
        :class:`~repro.core.errors.NumericalError`, which the fallback
        ladder then gets a chance to recover).  The output check forces a
        device sync, so it is opt-in — the serve layer validates at
        ``submit()`` and quarantines poisoned lanes itself.

        On a classified failure (see :mod:`repro.core.errors`) execution
        degrades along a bounded deterministic ladder — als→eig, then
        pallas→matfree, on numerical breakdown; donated→undonated then
        replanned-under-a-tighter-cap on runtime OOM — each hop emitted as
        an obs ``fallback`` event and counted in the metrics registry
        before the failing class is re-raised only once the ladder is
        exhausted.  A kernel that fails to lower or compile
        (:class:`~repro.core.errors.KernelError`) raises at once.
        """
        if not _obs.enabled():
            return self._execute(x, record=record, donate=donate,
                                 validate=validate)
        attrs = self.__dict__.get("_obs_attrs")
        if attrs is None:
            # static per-plan span attributes, built once: the properties
            # walk the schedule and would otherwise run on every execute
            attrs = self._obs_attrs = dict(
                shape=list(self.shape), dtype=self.dtype,
                backend=self.backend, variant=self.config.variant,
                adaptive=self.is_adaptive,
                predicted_s=self.total_predicted_s,
                peak_bytes=self.peak_bytes, als_passes=self.als_passes)
        with _obs.span("execute", record=record, **attrs):
            return self._execute(x, record=record, donate=donate,
                                 validate=validate)

    def _execute(self, x: jax.Array, *, record: bool = False,
                 donate: bool | None = None,
                 validate: str | None = None) -> SthosvdResult:
        xin = x
        x = jnp.asarray(x)
        if tuple(x.shape) != self.shape:
            raise InputError(f"plan is for shape {self.shape}, got {x.shape}")
        if str(x.dtype) != self.dtype:
            raise InputError(f"plan is for dtype {self.dtype}, got {x.dtype}")
        if validate not in (None, "none", "finite"):
            raise ValueError(
                f"validate must be None, 'none' or 'finite', got {validate!r}")
        if validate == "finite":
            check_finite(x, name="input")
        if self.is_adaptive:
            try:
                return self._execute_adaptive(x, record=record)
            except Exception as e:
                terr = classify_exception(e)
                if terr is not None and terr is not e:
                    raise terr from e
                raise
        created = x is not xin

        def can_retry() -> bool:
            # a failed donated sweep consumed the device copy; retry is
            # possible only while the caller's original buffer survives to
            # re-materialize from (always true for host inputs)
            nonlocal x, created
            d = getattr(x, "is_deleted", None)
            if d is None or not d():
                return True
            x2 = jnp.asarray(xin)
            d2 = getattr(x2, "is_deleted", None)
            if d2 is not None and d2():
                return False
            x, created = x2, x2 is not xin
            return True

        def run(p: "TuckerPlan", donate_override: bool | None) -> SthosvdResult:
            # sys.modules probe: plans that never meet repro.tune pay nothing
            tune = sys.modules.get("repro.tune")
            sink = tune.active_sink() if tune is not None else None
            if record or sink is not None:
                return p._execute_recorded(x, sink)
            donate_now = p._resolve_donate(created=created,
                                           override=donate_override)
            _chaos.fire("sweep", backend=p.backend)
            core, factors = p._sweep(batched=False, donate=donate_now)(
                p._place_input(x))
            if _chaos.active() and _chaos.poison("sweep_out",
                                                 backend=p.backend):
                core = core * float("nan")
            if validate == "finite":
                check_result_finite(core, factors,
                                    context=f"{p.config.variant} sweep")
            return SthosvdResult(
                tucker=TuckerTensor(core=core, factors=list(factors)),
                trace=[ModeTrace(s.mode, s.method, s.i_n, s.r_n, s.j_n, 0.0,
                                 backend=s.backend,
                                 predicted_s=s.predicted_s)
                       for s in p.schedule],
                select_overhead_s=0.0)

        return _run_with_fallback(self, can_retry, run, donate)

    def _execute_recorded(self, x: jax.Array, sink=None) -> SthosvdResult:
        """The plan's own sweep run eagerly with a :class:`StepTimer` as its
        solve: per-step wall-clock in the trace; feeds the active tune sink
        (if any) so executed plans become training records."""
        cfg = self.config
        if cfg.compute_dtype:
            x = x.astype(jnp.dtype(cfg.compute_dtype))
        timer = StepTimer()
        core, factors = _plan_sweep(self, timer)(self._place_input(x))
        trace = [ModeTrace(s.mode, s.method, s.i_n, s.r_n, s.j_n, dt,
                           backend=s.backend, predicted_s=s.predicted_s)
                 for s, dt in zip(self.schedule, timer.seconds)]
        if sink is not None:
            sink.add_traces(trace, platform=timer.platform,
                            dtype=cfg.compute_dtype or self.dtype,
                            order=len(self.shape), als_iters=cfg.als_iters)
        return SthosvdResult(
            tucker=TuckerTensor(core=core, factors=list(factors)),
            trace=trace, select_overhead_s=0.0)

    def resolve_ranks(self, x: jax.Array) -> tuple[tuple[int, ...], float]:
        """Run ONLY the sketch pass on ``x``: the per-mode ranks the policy
        chooses for this input plus the certified relative-error bound —
        without building the decomposition.  Adaptive plans only."""
        if not self.is_adaptive:
            raise ValueError("resolve_ranks needs a rank-adaptive plan "
                             "(TuckerConfig(error_target=...)); this plan's "
                             f"ranks are fixed at {self.config.ranks}")
        ranks, tails, *_ = self._sketch_pass(jnp.asarray(x))
        return ranks, math.sqrt(sum(tails.values()))

    def _sketch_pass(self, x: jax.Array):
        """The rank-adaptive sweep core: sequential randomized sketches
        (:func:`repro.core.solvers.rand_sketch`) in schedule order, reading
        each mode's rank off its sketched eigenvalue tail.

        Per step, the captured energy of a rank-r truncation of the current
        tensor equals the sum of the top-r eigenvalues of the sketched Gram
        — EXACT for the factor actually used, not an estimate — so the
        smallest grid candidate whose discarded energy fits the step's
        budget ``tau·||X||²`` is chosen (the grid cap when none fits).
        ``||X||²`` is the energy measured at step 0, before anything was
        truncated, which makes ``sqrt(Σ_n tail_n)`` of the recorded
        fractional tails a guaranteed relative-error bound via the
        sequential HOSVD inequality ``||X − X̂||² ≤ Σ_n τ_n²``.

        The sketch width is INPUT-ADAPTIVE: each mode starts narrow and
        doubles only while no candidate ≤ the current width meets the
        budget (up to ``rank cap + oversample``).  A narrower sketch can
        only under-capture — the measured tail of the factor it yields is
        still exact — so widening never weakens the guarantee, and
        well-compressible inputs never pay for the rank cap (without a
        ``ranks``/``rank_grid`` hint the cap is the full mode dimension;
        a full-width sketch there would erase the sketch's whole
        linear-in-I_n advantage).  Doubling keeps total sketch work within
        2× of the final width's.

        The host waits on the device exactly once per sketch width tried:
        one read of that width's eigenvalues and energy, the inputs of the
        rank decision (a ``sketch.readback`` span; the ``sketch`` span's
        ``syncs`` counts them).  The Ritz rotation and shrink that finish a
        mode are one compiled program (:func:`repro.core.solvers.ritz_shrink`)
        dispatched without a wait, so the next mode's sketch queues behind
        it while the device is still busy.  A step's wall-clock therefore
        ends at the read that settles its rank; the device time of its
        shrink is counted in the next step, whose first read waits for it.
        The last shrink is waited on by whoever reads the result: the
        caller on the ``methods="rand"`` path, the refine sweep (queued
        behind it) otherwise.

        Returns ``(ranks, tails, factors, core, seconds, js, missed)``:
        per-mode chosen ranks and fractional tails, the sketch's own
        orthonormal factors, the shrunk core, per-step wall-clock, the
        actual (shrunk) J_n each step saw, and the modes whose budget NO
        grid candidate met even at the cap width — the error-target miss
        that triggers the rand→eig ladder hop in :meth:`_execute_adaptive`.
        """
        import time as _time

        import numpy as np

        from .solvers import rand_sketch, ritz_shrink, sketch_readout
        cfg = self.config
        if cfg.compute_dtype:
            x = x.astype(jnp.dtype(cfg.compute_dtype))
        wdtype = x.dtype
        y = x
        total = None
        chosen: dict[int, int] = {}
        tails: dict[int, float] = {}
        factors: dict[int, jax.Array] = {}
        seconds: list[float] = []
        js: list[int] = []
        missed: list[int] = []
        platform = jax.default_backend()
        for s in self.schedule:
            with _obs.span("sketch", mode=s.mode, solver="rand",
                           backend=s.backend, platform=platform, i_n=s.i_n,
                           predicted_s=s.predicted_s) as sp:
                t0 = _time.perf_counter()
                _chaos.fire("sketch", mode=s.mode)
                js.append(int(y.size // y.shape[s.mode]))
                width_cap = min(s.i_n, s.rank_grid[-1] + cfg.oversample)
                width = min(width_cap, max(16, 2 * cfg.oversample,
                                           s.rank_grid[0] + cfg.oversample))
                widths = syncs = 0
                while True:
                    q, b, evals, vecs, energy = rand_sketch(
                        y, s.mode, width, power_iters=cfg.power_iters,
                        impl=s.backend)
                    widths += 1
                    packed = sketch_readout(evals, energy)
                    # the host waits here for the sketch it reads, and for
                    # all queued before it (the previous mode's shrink)
                    with _obs.span("sketch.readback", mode=s.mode,
                                   width=int(width)):
                        read = np.asarray(packed, dtype=np.float64)
                    syncs += 1
                    ev, energy = np.maximum(read[:-1], 0.0), float(read[-1])
                    if total is None:
                        # step 0: ||X||², the budget basis
                        total = energy or 1.0
                    csum = np.cumsum(ev[::-1])  # csum[r-1] = top-r captured
                    budget = s.tau * total
                    r = tail = None
                    for cand in s.rank_grid:    # ascending: smallest fit wins
                        if cand > width:
                            break
                        t = max(energy - float(csum[cand - 1]), 0.0)
                        if t <= budget:
                            r, tail = cand, t
                            break
                    if r is not None or width >= width_cap:
                        break
                    width = min(2 * width, width_cap)
                if r is None:   # no candidate fits even at the cap width: take
                                # the largest grid rank the sketch can express
                    r = max(g for g in s.rank_grid if g <= width)
                    tail = max(energy - float(csum[r - 1]), 0.0)
                    missed.append(s.mode)
                chosen[s.mode], tails[s.mode] = int(r), tail / total
                dt = _time.perf_counter() - t0
                # top-r Ritz rotation of the range basis; shrink via the
                # already-projected b — no second pass over the input
                factors[s.mode], y = ritz_shrink(
                    q, b, vecs, s.mode, int(r), dtype=wdtype, impl=s.backend)
                sp.set(rank=int(r), tail_err=tail / total, width=int(width),
                       j_n=js[-1], widths=widths, syncs=syncs)
            seconds.append(dt)
            _drift.MONITOR.observe(platform=platform, backend=s.backend,
                                   solver="rand",
                                   predicted_s=s.predicted_s, actual_s=dt,
                                   source="execute")
        ranks = tuple(chosen[m] for m in range(len(self.shape)))
        return ranks, tails, factors, y, seconds, js, missed

    def _execute_adaptive(self, x: jax.Array, *,
                          record: bool = False) -> SthosvdResult:
        """Two-phase rank-adaptive execution (never donates — the original
        input is read again by the refinement sweep).

        Phase 1 resolves ranks per mode (:meth:`_sketch_pass`).  Phase 2:
        with ``methods="rand"`` the sketch's own factors and shrunk core
        ARE the result — the fastest path, certified by the measured bound;
        any other ``methods`` re-plans at the chosen FIXED ranks and runs
        the ordinary compiled eig/als sweep as refinement, with the sketch
        cost reported as ``select_overhead_s`` and the measured per-mode
        tails riding the refined trace as ``tail_err`` labels for the tune
        store."""
        cfg = self.config
        xa = jnp.asarray(x)
        ranks, tails, factors, core, seconds, js, missed = \
            self._sketch_pass(xa)
        bound = math.sqrt(sum(tails.values()))
        m = cfg.methods
        sketch_only = m == "rand" or \
            (not isinstance(m, str) and all(q == "rand" for q in m))
        hop_methods = None
        if sketch_only and missed:
            # rand→eig ladder hop: the sketch missed its per-mode budget at
            # the cap width on these modes, so instead of shipping the
            # under-converged sketch factors, refine deterministically at
            # the chosen (cap) ranks.  The reported bound stays the
            # measured sketch bound — honest about the miss (> target)
            # rather than silently optimistic.
            hop_methods = "eig"
            sketch_only = False
            _obs.event("fallback", hop="rand_to_eig",
                       modes=[int(mm) for mm in missed],
                       shape=list(self.shape), backend=self.backend)
            _metrics.REGISTRY.counter(
                "atucker_fallback_hops_total",
                "execute-time fallback ladder hops, by rung").inc(
                    hop="rand_to_eig", backend=self.backend)
        if not sketch_only:
            rcfg = replace(cfg, ranks=ranks, error_target=None,
                           rank_grid=None,
                           mode_order=tuple(s.mode for s in self.schedule))
            if hop_methods is not None:
                rcfg = replace(rcfg, methods=hop_methods)
            res = _spanned_plan(self.shape, self.dtype, rcfg,
                                refine=True).execute(
                xa, record=record, donate=False)
            for t in res.trace:
                t.tail_err = tails[t.mode]
            return SthosvdResult(
                tucker=res.tucker, trace=res.trace,
                select_overhead_s=res.select_overhead_s + sum(seconds),
                error_bound=bound)
        n = len(self.shape)
        trace = [ModeTrace(s.mode, "rand", s.i_n, ranks[s.mode], j, dt,
                           backend=s.backend, predicted_s=s.predicted_s,
                           tail_err=tails[s.mode])
                 for s, j, dt in zip(self.schedule, js, seconds)]
        tune = sys.modules.get("repro.tune")
        sink = tune.active_sink() if tune is not None else None
        if sink is not None:
            sink.add_traces(trace, platform=jax.default_backend(),
                            dtype=cfg.compute_dtype or self.dtype,
                            order=n, als_iters=cfg.als_iters)
        return SthosvdResult(
            tucker=TuckerTensor(core=core,
                                factors=[factors[mm] for mm in range(n)]),
            trace=trace, select_overhead_s=0.0, error_bound=bound)

    def execute_batch(self, xs: jax.Array, *,
                      donate: bool | None = None) -> list[SthosvdResult]:
        """Decompose a fleet of same-shaped tensors (leading batch axis) with
        one vmapped program; returns one result per batch element.

        Sharded plans run the fleet item by item instead (shard_map
        schedules don't vmap) — each item still reuses the one cached
        compiled sweep, so the fleet pays a single compilation.

        ``donate`` behaves as in :meth:`execute`, applied to the whole
        stacked batch buffer (donating a fleet an engine stacked itself is
        free memory back)."""
        xin = xs
        xs = jnp.asarray(xs)
        if tuple(xs.shape[1:]) != self.shape:
            raise ValueError(
                f"plan is for batches of shape {self.shape}, got {xs.shape}")
        if str(xs.dtype) != self.dtype:
            raise ValueError(f"plan is for dtype {self.dtype}, got {xs.dtype}")
        if self.backend == "sharded" or self.is_adaptive:
            # adaptive: item by item — the policy may choose different
            # ranks per tensor, so there is no one vmappable program
            return [self.execute(xs[b]) for b in range(xs.shape[0])]
        donate_now = self._resolve_donate(created=xs is not xin,
                                          override=donate)
        cores, factors = self._sweep(batched=True, donate=donate_now)(xs)
        out = []
        for b in range(xs.shape[0]):
            out.append(SthosvdResult(
                tucker=TuckerTensor(core=cores[b],
                                    factors=[u[b] for u in factors]),
                trace=[ModeTrace(s.mode, s.method, s.i_n, s.r_n, s.j_n, 0.0,
                                 backend=s.backend, predicted_s=s.predicted_s)
                       for s in self.schedule],
                select_overhead_s=0.0))
        return out

    __call__ = execute

    # -- derivation ----------------------------------------------------------
    def for_shape(self, shape: Sequence[int], *,
                  selector: Callable[..., str] | None = None,
                  keep_methods: bool = False) -> "TuckerPlan":
        """This plan's config/dtype re-planned at a different ``shape`` — the
        plan-reuse hook for the serve layer's shape buckets, where a bucket's
        warm plan spawns plans for the member shapes padded into it.

        By default the selector and mode order re-resolve against the new
        per-mode problem sizes, so the derived plan is indistinguishable from
        ``plan(shape, self.dtype, self.config)`` — same schedule, same cached
        compiled sweep, bitwise-identical execution to a direct plan (what
        the exact pad mode relies on).  ``keep_methods=True`` instead pins
        this plan's resolved per-mode solvers and frozen sweep order onto
        the new shape: zero selector calls, at the price of solver choices
        tuned for the bucket shape, not the member's.
        """
        shape = tuple(int(s) for s in shape)
        if len(shape) != len(self.shape):
            raise ValueError(
                f"plan is for an order-{len(self.shape)} tensor; cannot "
                f"derive an order-{len(shape)} plan (shape {shape})")
        if shape == self.shape:
            return self
        cfg = self.config
        if keep_methods:
            order = tuple(s.mode for s in self.schedule[:len(self.shape)])
            if self.is_adaptive:
                # the policy IS the method; pin only the sweep order
                # (config.methods stays the refinement solver choice)
                cfg = replace(cfg, mode_order=order)
            else:
                cfg = replace(cfg, methods=self.methods, mode_order=order)
        return plan(shape, self.dtype, cfg, selector=selector)

    # -- reporting -----------------------------------------------------------
    def describe(self) -> str:
        """Human-readable plan report: the frozen schedule in execution
        order with modeled cost and per-device peak per step, plus the
        totals, donation policy, and memory cap the plan was built under."""
        cfg = self.config
        cap = cfg.memory_cap_bytes
        head = (f"error_target={cfg.error_target:g} (rank-adaptive)"
                if self.is_adaptive else f"ranks {cfg.ranks}")
        lines = [
            f"TuckerPlan {self.shape} {self.dtype} -> {head} "
            f"[{cfg.variant}, backend={self.backend}]",
            f"  mode_order={cfg.mode_order!r}  "
            + (f"mode_parallel={cfg.mode_parallel!r}  "
               if cfg.mode_parallel != "off" else "")
            + f"memory_cap_bytes={cap if cap is not None else 'uncapped'}  "
            f"donate_input={'auto' if cfg.donate_input is None else cfg.donate_input}"
            + (" (resolves: donated for host inputs; a caller-held jax "
               "array is kept)" if self.donates and cfg.donate_input is None
               else f" (resolves: {'donated' if self.donates else 'undonated'})"),
        ]
        if self.is_adaptive:
            lines.append(
                f"  rank policy: tau²={self.schedule[0].tau:.3g}·||X||² "
                f"per mode  oversample={cfg.oversample}  "
                f"power_iters={cfg.power_iters}  "
                "(steps sized at grid caps; ranks resolve per input)")
        per_dev = any(s.n_shards > 1 for s in self.schedule)
        for k, s in enumerate(self.schedule):
            pred = f"  pred={s.predicted_s * 1e3:.3f}ms" if s.predicted_s \
                else ""
            shard = f"  shard_mode={s.shard_mode}/{s.n_shards}" \
                if per_dev else ""
            grp = f"  ∥group={s.group}" if s.group is not None else ""
            pol = (f"  grid={s.rank_grid[0]}..{s.rank_grid[-1]}"
                   f"({len(s.rank_grid)})"
                   if s.rank_grid is not None else "")
            lines.append(
                f"  step {k}: mode {s.mode} {s.method:>3s}  "
                f"I={s.i_n} R={s.r_n} J={s.j_n}  "
                f"flops={s.flops:.3g}  peak={s.peak_bytes:,}B"
                f"{shard}{grp}{pol}{pred}")
        total_pred = self.total_predicted_s
        lines.append(
            f"  total: flops={self.total_flops:.3g}  "
            f"peak={self.peak_bytes:,}B"
            + (" (per device)" if per_dev else "")
            + (f"  predicted={total_pred * 1e3:.3f}ms" if total_pred else "")
            + (f"  cap_headroom={cap - self.peak_bytes:,}B"
               if cap is not None else ""))
        return "\n".join(lines)

    # -- persistence (mirrors Selector.save) ---------------------------------
    def to_dict(self) -> dict:
        return {"version": PLAN_FORMAT_VERSION, "shape": list(self.shape),
                "dtype": self.dtype, "config": self.config.to_dict(),
                "schedule": [s.to_dict() for s in self.schedule],
                "select_seconds": self.select_seconds}

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    @classmethod
    def from_dict(cls, d: dict) -> "TuckerPlan":
        if d.get("version", 1) > PLAN_FORMAT_VERSION:
            raise ValueError(f"plan format {d['version']} newer than supported "
                             f"{PLAN_FORMAT_VERSION}")
        return cls(shape=tuple(d["shape"]), dtype=d["dtype"],
                   config=TuckerConfig.from_dict(d["config"]),
                   schedule=tuple(ModeStep.from_dict(s) for s in d["schedule"]),
                   select_seconds=d.get("select_seconds", 0.0))

    @classmethod
    def from_json(cls, s: str) -> "TuckerPlan":
        return cls.from_dict(json.loads(s))

    def save(self, path: str | Path) -> None:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(self.to_json())

    @classmethod
    def load(cls, path: str | Path) -> "TuckerPlan":
        return cls.from_json(Path(path).read_text())


# ---------------------------------------------------------------------------
# plan / decompose
# ---------------------------------------------------------------------------

def _resolve_rank_policy(shape: tuple[int, ...],
                         config: TuckerConfig) -> tuple[tuple, tuple]:
    """Per-mode candidate grids + sizing caps for a rank-adaptive config.

    The cap (each step's ``r_n`` — what scratch/peak modeling and the
    schedule DP see) is the largest candidate: ``ranks`` when given, else
    the grid maximum, else the full mode dimension.  A flat int
    ``rank_grid`` is one shared grid applied to every mode; a tuple of
    tuples is per-mode.  Candidates are deduplicated, clamped to
    ``[1, cap]``, and sorted ascending — the execute-time budget check
    walks them smallest-first."""
    n = len(shape)
    rg = config.rank_grid
    if rg is not None and all(isinstance(g, int) for g in rg):
        rg = tuple(rg for _ in range(n))
    if rg is not None and len(rg) != n:
        raise ValueError(f"rank_grid has {len(rg)} mode entries for an "
                         f"order-{n} tensor of shape {shape}")
    if config.ranks is not None and len(config.ranks) != n:
        raise ValueError(f"ranks {config.ranks} do not match order-{n} "
                         f"shape {shape}")
    grids = []
    for m in range(n):
        hi = shape[m] if config.ranks is None \
            else max(1, min(int(config.ranks[m]), shape[m]))
        if rg is None:
            g = tuple(range(1, hi + 1))
        else:
            g = tuple(sorted({max(1, min(int(r), hi)) for r in rg[m]}))
        grids.append(g)
    return tuple(grids), tuple(g[-1] for g in grids)


def _plan_adaptive(shape: tuple[int, ...], dtype,
                   config: TuckerConfig) -> TuckerPlan:
    """Rank-adaptive planning: freeze a rank POLICY, not ranks.

    The schedule is sized at each mode's rank CAP (see
    :func:`_resolve_rank_policy`) — the conservative figure for scratch
    modeling and ``memory_cap_bytes`` — with every step pinned to the
    ``rand`` sketch solver.  ``mode_order="opt"`` runs the schedule DP with
    the rank grid as its third decision axis
    (:func:`repro.core.schedule_opt.optimize_schedule`), so the sweep order
    is chosen for the policy, not just the caps.  Each step then carries
    its ``rank_grid`` and the equi-partitioned HOSVD budget share
    ``tau = error_target²/N``; the actual ranks resolve per input at
    execute time (:meth:`TuckerPlan._sketch_pass`)."""
    import time as _time
    n = len(shape)
    compute_dtype = jnp.dtype(config.compute_dtype) if config.compute_dtype \
        else dtype
    backend = resolve_backend(config.impl, dtype=compute_dtype)
    if not backend.supports_solver("rand"):
        raise ValueError(f"backend {backend.name!r} cannot run the 'rand' "
                         "sketch solver rank-adaptive plans are built on "
                         f"(capabilities: {backend.solvers})")
    grids, caps = _resolve_rank_policy(shape, config)
    from .selector import default_selector
    cost_model = default_selector(backend=backend.name).cost_model
    t0 = _time.perf_counter()
    mode_order = config.mode_order
    if mode_order == "opt":
        from .schedule_opt import optimize_schedule
        mode_order = optimize_schedule(
            shape, caps, methods=["rand"] * n, als_iters=config.als_iters,
            itemsize=compute_dtype.itemsize, cost_model=cost_model,
            memory_cap_bytes=config.memory_cap_bytes,
            rank_grid=grids).order
    schedule = resolve_schedule(
        shape, caps, variant="sthosvd", methods="rand",
        mode_order=mode_order, als_iters=config.als_iters,
        itemsize=compute_dtype.itemsize, backend=backend.name,
        n_shards=1, cost_model=cost_model,
        memory_cap_bytes=config.memory_cap_bytes)
    tau = float(config.error_target) ** 2 / n
    schedule = tuple(replace(s, rank_grid=grids[s.mode], tau=tau)
                     for s in schedule)
    return TuckerPlan(shape=shape, dtype=str(dtype), config=config,
                      schedule=schedule,
                      select_seconds=_time.perf_counter() - t0)


def plan(shape: Sequence[int], dtype, config: TuckerConfig, *,
         selector: Callable[..., str] | None = None) -> TuckerPlan:
    """Resolve ``config`` against a concrete (shape, dtype) → ``TuckerPlan``.

    All selector/cost-model queries happen here, against the statically known
    per-mode problem sizes, and ``config.impl`` (possibly ``"auto"``) is
    resolved through the backend registry against the current platform,
    compute dtype, and mesh; ``TuckerPlan.execute`` never selects or
    resolves again.  With a mesh (``impl="sharded"``, or ``"auto"`` when
    one is attached) the shard-mode schedule is frozen here too: per-step
    shard choice, reshard points, and per-device ``peak_bytes``.

    A config with ``error_target=`` routes to rank-ADAPTIVE planning
    (:func:`_plan_adaptive`): the plan freezes a rank policy and sweep
    order; per-mode ranks resolve per input at execute time.
    """
    return _spanned_plan(shape, dtype, config, selector=selector)


def _spanned_plan(shape: Sequence[int], dtype, config: TuckerConfig, *,
                  selector: Callable[..., str] | None = None,
                  refine: bool = False) -> TuckerPlan:
    """:func:`plan` inside a ``plan`` span; ``refine`` marks the re-plan at
    the resolved ranks that a rank-adaptive execute makes."""
    if not _obs.enabled():
        return _plan(shape, dtype, config, selector=selector)
    with _obs.span("plan", shape=[int(s) for s in shape],
                   dtype=str(jnp.dtype(dtype)), impl=config.impl,
                   variant=config.variant,
                   mode_order=str(config.mode_order),
                   adaptive=config.error_target is not None,
                   refine=refine) as sp:
        p = _plan(shape, dtype, config, selector=selector)
        sp.set(backend=p.backend, n_steps=len(p.schedule),
               methods=list(p.methods), select_s=p.select_seconds,
               predicted_s=p.total_predicted_s, peak_bytes=p.peak_bytes)
        return p


def _plan(shape: Sequence[int], dtype, config: TuckerConfig, *,
          selector: Callable[..., str] | None = None) -> TuckerPlan:
    shape = tuple(int(s) for s in shape)
    dtype = jnp.dtype(dtype)
    if config.error_target is not None:
        return _plan_adaptive(shape, dtype, config)
    compute_dtype = jnp.dtype(config.compute_dtype) if config.compute_dtype \
        else dtype
    backend = resolve_backend(config.impl, dtype=compute_dtype,
                              mesh=config.mesh)
    if backend.requires_mesh and config.variant != "sthosvd":
        raise ValueError(f"backend {backend.name!r} supports variant "
                         f"'sthosvd' only, got {config.variant!r}")
    # selector resolution sees the RESOLVED backend: a per-backend trained
    # model (repro.tune) outranks the platform-pooled one, and its embedded
    # (possibly calibrated) cost model prices the schedule either way
    from .selector import default_selector
    timed = None
    if config.methods == "auto":
        if selector is None:
            selector = default_selector(backend=backend.name)
        selector = timed = TimedSelector(selector)
    cost_model = getattr(selector, "cost_model", None) or \
        default_selector(backend=backend.name).cost_model
    mp: str | int = config.mode_parallel
    if not backend.requires_mesh and mp != "off":
        if mp == "auto":
            mp = "off"   # single device: sequential shrinking always wins
        else:
            raise ValueError(
                f"mode_parallel={mp} needs a sharded backend (attach a "
                f"mesh); impl resolved to {backend.name!r}")
    schedule = resolve_schedule(
        shape, config.ranks, variant=config.variant, methods=config.methods,
        mode_order=config.mode_order, selector=selector,
        als_iters=config.als_iters, hooi_iters=config.hooi_iters,
        itemsize=compute_dtype.itemsize, backend=backend.name,
        n_shards=config.n_shards if backend.requires_mesh else 1,
        cost_model=cost_model, memory_cap_bytes=config.memory_cap_bytes,
        mode_parallel=mp)
    p = TuckerPlan(shape=shape, dtype=str(dtype), config=config,
                   schedule=schedule,
                   select_seconds=timed.seconds if timed else 0.0)
    if config.memory_cap_bytes is not None and \
            p.peak_bytes > config.memory_cap_bytes:
        # every step fits, but the plan-level (donation-aware) peak does
        # not: an undonated sweep keeps the dead input copy live through
        # steps 1..N-1 on top of each step's working set
        from .schedule_opt import MemoryCapError
        raise MemoryCapError(
            f"schedule fits memory_cap_bytes={config.memory_cap_bytes:,} "
            f"per step, but the undonated sweep's modeled peak is "
            f"{p.peak_bytes:,} bytes — the caller-held input copy "
            f"({p.input_bytes:,} bytes) rides on every step after the "
            "first; enable donation (donate_input=True or the default "
            "auto policy with host inputs) or raise the cap")
    return p


# ---------------------------------------------------------------------------
# Execute-time fallback ladder
# ---------------------------------------------------------------------------

def _replan_safe(p: "TuckerPlan", cfg: TuckerConfig) -> "TuckerPlan | None":
    """Plan a ladder hop's degraded config, or None when the hop itself
    cannot be planned (e.g. the tighter cap admits no schedule) — the
    ladder then moves on / gives up rather than masking the original
    failure with a planning error."""
    try:
        return plan(p.shape, p.dtype, cfg)
    except Exception:
        return None


def _next_hop(p: "TuckerPlan", err: BaseException,
              applied: list[str]) -> "tuple[str, TuckerPlan] | None":
    """Pick the next ladder rung for a classified failure, or None when the
    ladder is exhausted (each rung applies at most once, in a fixed order,
    so the ladder is bounded and deterministic)."""
    cfg = p.config
    has_pallas = any(s.backend == "pallas" for s in p.schedule)

    def to_matfree():
        if has_pallas and "pallas_to_matfree" not in applied:
            p2 = _replan_safe(p, replace(cfg, impl="matfree"))
            if p2 is not None:
                return "pallas_to_matfree", p2
        return None

    if isinstance(err, NumericalError):
        if "als_to_eig" not in applied and \
                any(s.method == "als" for s in p.schedule):
            methods = tuple("eig" if m == "als" else m for m in p.methods)
            p2 = _replan_safe(p, replace(cfg, methods=methods))
            if p2 is not None:
                return "als_to_eig", p2
        return to_matfree()
    if isinstance(err, ResourceError):
        # rung 1 retries the SAME schedule with donation forced off (an
        # aliased buffer is the usual marginal allocation); rung 2 replans
        # the whole sweep under a tighter per-device cap
        if "donate_off" not in applied:
            return "donate_off", p
        if "replan_cap" not in applied:
            current = cfg.memory_cap_bytes or p.peak_bytes
            cap = max(1, int(0.75 * current))
            p2 = _replan_safe(p, replace(cfg, memory_cap_bytes=cap,
                                         mode_order="opt"))
            if p2 is not None:
                return "replan_cap", p2
        return None
    # a kernel that failed to lower or compile, or an unclassified failure:
    # no hop — moving the sweep onto another backend would let a run that
    # asked for the kernels pass without ever running one
    return None


def _emit_hop(p: "TuckerPlan", name: str, err: BaseException) -> None:
    _obs.event("fallback", hop=name, error=type(err).__name__,
               shape=list(p.shape), backend=p.backend)
    _metrics.REGISTRY.counter(
        "atucker_fallback_hops_total",
        "execute-time fallback ladder hops, by rung").inc(
            hop=name, backend=p.backend)


def _run_with_fallback(p0: "TuckerPlan", can_retry, run,
                       donate_override: bool | None) -> SthosvdResult:
    """Drive ``run(plan, donate)`` through the fallback ladder: classify
    each failure, degrade one rung at a time, re-raise the classified error
    once no rung remains.  Input-side failures (bad input, deadline,
    cancellation) never hop — retrying cannot fix the caller's data."""
    p, donate_now = p0, donate_override
    applied: list[str] = []
    while True:
        try:
            return run(p, donate_now)
        except Exception as e:  # noqa: BLE001 - classification is the point
            if isinstance(e, (InputError, DeadlineError, CancelledError)):
                raise
            terr = classify_exception(e)
            if not can_retry():
                # the failed sweep consumed the donated input buffer and no
                # original survives to re-materialize from — surface the
                # classification instead of hopping onto a dead input
                if terr is not None and terr is not e:
                    raise terr from e
                raise
            hop = _next_hop(p, terr if terr is not None else e, applied)
            if hop is None:
                if terr is not None and terr is not e:
                    raise terr from e
                raise
            name, p2 = hop
            applied.append(name)
            if name == "donate_off":
                donate_now = False
            _emit_hop(p, name, terr if terr is not None else e)
            # the degraded plan records through the same tune/obs machinery
            # as any other execute, so the flywheel learns the hop happened
            p = p2


def decompose(x: jax.Array, config: TuckerConfig, *,
              selector: Callable[..., str] | None = None) -> SthosvdResult:
    """One-shot convenience: ``plan(x.shape, x.dtype, config).execute(x)``.
    The compiled sweep is still cached process-wide, so repeated calls on
    same-shaped inputs only pay the (cheap) schedule resolution."""
    x = jnp.asarray(x)
    return plan(x.shape, x.dtype, config, selector=selector).execute(x)
