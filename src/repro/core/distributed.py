"""Distributed st-HOSVD for tensors sharded across a mesh (TuckerMPI pattern,
JAX-native) — the execution engine behind the ``sharded`` ops backend.

Decomposition of a tensor sharded along one mode over a mesh axis:

  * Gram (mode n ≠ shard mode m): each device contracts its local slab —
    the shard axis lives inside the merged contraction dims — giving a
    *partial* I_n×I_n Gram; one ``psum`` over the shard axis completes it.
    (Explicit ``shard_map`` so the collective schedule is visible.)
  * eigh on the replicated small Gram runs redundantly on every device
    (standard practice; I_n×I_n is tiny next to the tensor).
  * TTM (mode n ≠ m): embarrassingly local; output stays sharded on m.
  * Before processing the currently-sharded mode the tensor is resharded to
    the largest *remaining* mode (one all-to-all, amortized by the shrink).

The ALS path runs under GSPMD (sharding constraints inside jit) — its inner
TTM/TTT chain contracts sharded dims, and XLA inserts the same psum pattern
automatically; we keep it as the reference for the manual schedule.

The distribution *decisions* (which mode to shard per step, where the
reshards land) are frozen at plan time by
:func:`repro.core.plan.resolve_schedule` via :func:`pick_shard_mode`; this
module only executes frozen :class:`~repro.core.plan.ModeStep` schedules:

  * :func:`run_sharded_schedule` — eager per-step runner with real per-mode
    wall-clock (the legacy :func:`sthosvd_distributed` entry point).
  * :func:`sweep_sharded` — the same schedule as one pure function, compiled
    whole by ``TuckerPlan``'s process-wide sweep cache (zero recompiles on
    plan reuse, exactly like the single-device backends).
  * :func:`sweep_mode_parallel` — the group-aware sweep for schedules whose
    steps carry ``group`` ids: every member of a group computes its factor
    from the SAME un-shrunk tensor (all eig Grams fused into ONE shard_map
    with one psum each — one mesh barrier for the whole group instead of
    one per mode), then a single fused multi-TTM truncates all group modes
    at once.  Lower latency, more FLOPs; the plan-time DP
    (:mod:`repro.core.schedule_opt`) decides when that trade wins.
"""

from __future__ import annotations

import time
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.core import Tracer
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..obs import drift as _drift
from ..obs import trace as _obs
from . import tensor_ops as T
from .plan import ModeStep, solve_step
from .solvers import DEFAULT_ALS_ITERS, als_solve
from .sthosvd import ModeTrace, SthosvdResult, TuckerTensor


def _spec_for(ndim: int, mode: int | None, axis: str) -> P:
    parts = [None] * ndim
    if mode is not None:
        parts[mode] = axis
    return P(*parts)


def _reshard(x: jax.Array, mesh: Mesh, mode: int | None, axis: str) -> jax.Array:
    """Move ``x`` onto the mesh, sharded on ``mode`` (None = replicated).
    On an ``Explicit`` shard axis (what ``jax.make_mesh`` builds) this is
    ``jax.sharding.reshard``, eager or traced.  On an ``Auto`` axis it is a
    sharding constraint inside a jit trace (GSPMD inserts the all-to-all)
    and a device_put eagerly."""
    sh = NamedSharding(mesh, _spec_for(x.ndim, mode, axis))
    if _explicit(mesh, axis):
        return jax.sharding.reshard(x, sh)
    if isinstance(x, Tracer):
        return jax.lax.with_sharding_constraint(x, sh)
    return jax.device_put(x, sh)


def _explicit(mesh: Mesh, axis: str) -> bool:
    return mesh.axis_types[mesh.axis_names.index(axis)] == AxisType.Explicit


def _als_gspmd(y: jax.Array, step: ModeStep, mesh: Mesh, axis: str, *,
               als_iters: int):
    """ALS on a ``y`` sharded on ``step.shard_mode``, partitioned by GSPMD:
    its TTM/TTT chain contracts the sharded mode and XLA inserts the psums.
    An ``Explicit`` shard axis is switched to ``Auto`` for the solve
    (``auto_axes``), since explicit sharding types refuse a contraction
    over a sharded dim.  Returns ``(u, y_new)``, ``y_new`` on the same
    shard mode."""
    def solve(y):
        res = als_solve(y, step.mode, step.r_n, num_iters=als_iters)
        return res.u, res.y_new

    if not _explicit(mesh, axis):
        u, y_new = solve(y)
        return u, _reshard(y_new, mesh, step.shard_mode, axis)
    out = (NamedSharding(mesh, P()),
           NamedSharding(mesh, _spec_for(y.ndim, step.shard_mode, axis)))
    return jax.sharding.auto_axes(solve, axes=axis, out_sharding=out)(y)


@lru_cache(maxsize=256)
def _gram_psum(mesh: Mesh, axis: str, ndim: int, mode: int, shard_mode: int):
    """shard_map'd partial-Gram + psum over the shard axis (cached per
    (mesh, schedule-position) so eager reuse never rebuilds the jit)."""
    @jax.jit
    def run(x):
        def body(xl):
            s_local = T.gram(xl, mode)
            return jax.lax.psum(s_local, axis)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=_spec_for(ndim, shard_mode, axis),
            out_specs=P(),
        )(x)
    return run


@lru_cache(maxsize=256)
def _ttm_local(mesh: Mesh, axis: str, ndim: int, mode: int, shard_mode: int):
    """shard_map'd local TTM (contraction mode fully local)."""
    @jax.jit
    def run(x, ut):
        def body(xl, utl):
            return T.ttm(xl, utl, mode)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(_spec_for(ndim, shard_mode, axis), P()),
            out_specs=_spec_for(ndim, shard_mode, axis),
        )(x, ut)
    return run


@lru_cache(maxsize=256)
def _gram_group_psum(mesh: Mesh, axis: str, ndim: int, modes: tuple,
                     shard_mode: int):
    """ONE shard_map producing every group member's psum'd Gram from the
    same local slab — the mode-parallel latency win: a single mesh barrier
    amortized over ``len(modes)`` Grams instead of one barrier each."""
    @jax.jit
    def run(x):
        def body(xl):
            return tuple(jax.lax.psum(T.gram(xl, m), axis) for m in modes)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=_spec_for(ndim, shard_mode, axis),
            out_specs=tuple(P() for _ in modes),
        )(x)
    return run


@lru_cache(maxsize=256)
def _ttm_group_local(mesh: Mesh, axis: str, ndim: int, modes: tuple,
                     shard_mode: int):
    """shard_map'd fused multi-TTM: chain every group member's truncation
    over the local slab in one program (all contraction modes ≠ the shard
    mode, so no collective is needed at all)."""
    @jax.jit
    def run(x, *uts):
        def body(xl, *utl):
            for m, u in zip(modes, utl):
                xl = T.ttm(xl, u, m)
            return xl
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(_spec_for(ndim, shard_mode, axis),)
            + (P(),) * len(modes),
            out_specs=_spec_for(ndim, shard_mode, axis),
        )(x, *uts)
    return run


def pick_shard_mode(shape: tuple[int, ...], exclude: int, n_shards: int) -> int | None:
    """Largest mode ≠ ``exclude`` divisible by the shard count; None → the
    (shrunk) tensor no longer shards evenly and is cheap enough to replicate
    — st-HOSVD's sequential shrinking makes the late modes tiny."""
    return pick_shard_mode_group(shape, (exclude,), n_shards)


def pick_shard_mode_group(shape: tuple[int, ...], exclude,
                          n_shards: int) -> int | None:
    """Largest mode outside ``exclude`` (an iterable of modes) divisible by
    the shard count.  A mode-parallel group's shard mode must lie OUTSIDE
    the group: the Gram of the sharded mode itself would need an all-gather,
    so a group covering every shardable mode runs replicated (``None``) —
    the memory model prices exactly that, which is how a per-device cap can
    refuse an all-modes group."""
    excluded = frozenset(exclude)
    order = sorted(range(len(shape)), key=lambda m: -shape[m])
    for m in order:
        if m not in excluded and shape[m] % n_shards == 0:
            return m
    return None


# ---------------------------------------------------------------------------
# Frozen-schedule execution (shared by the plan layer and the legacy entry)
# ---------------------------------------------------------------------------

def _eig_u(s: jax.Array, r_n: int, dtype) -> jax.Array:
    """Top-r_n eigvecs of a (replicated) Gram, descending, in compute dtype."""
    _, vecs = jnp.linalg.eigh(
        s.astype(jnp.promote_types(s.dtype, jnp.float32)))
    return vecs[:, -r_n:][:, ::-1].astype(dtype)


def solve_step_sharded(y: jax.Array, step: ModeStep, mesh: Mesh, axis: str,
                       *, als_iters: int = DEFAULT_ALS_ITERS):
    """One frozen mode solve on the mesh: reshard to the step's recorded
    shard mode, then run its solver's collective schedule.  Returns
    ``(u, y_new)`` with ``y_new`` sharded on ``step.shard_mode``.

    Works both eagerly (``run_sharded_schedule``) and under an enclosing jit
    trace (``sweep_sharded``): resharding becomes a device_put or a GSPMD
    constraint accordingly.
    """
    n = y.ndim
    y = _reshard(y, mesh, step.shard_mode, axis)
    if step.shard_mode is None:
        # replicated fallback: every device runs the plain local solve
        # (matfree primitives — same contract as the single-device path)
        res = solve_step(y, step, als_iters=als_iters, impl="matfree")
        return res.u, res.y_new
    if step.method == "eig":
        s = _gram_psum(mesh, axis, n, step.mode, step.shard_mode)(y)
        u = _eig_u(s, step.r_n, y.dtype)
        y = _ttm_local(mesh, axis, n, step.mode, step.shard_mode)(y, u.T)
        return u, y
    if step.method == "als":
        return _als_gspmd(y, step, mesh, axis, als_iters=als_iters)
    raise ValueError(f"unknown distributed method {step.method!r}")


def solve_group_sharded(y: jax.Array, group, mesh: Mesh, axis: str, *,
                        als_iters: int = DEFAULT_ALS_ITERS):
    """One frozen mode-parallel group on the mesh: every member's factor is
    computed from the SAME un-shrunk tensor — all eig Grams through ONE
    fused shard_map+psum, ALS members under GSPMD against the shared input
    — then a single fused multi-TTM truncates every group mode at once.
    Returns ``(factors, y_new)`` with ``factors`` keyed by mode and
    ``y_new`` sharded on the group's (shared) shard mode.

    Like :func:`solve_step_sharded` this works both eagerly and under an
    enclosing jit trace.
    """
    n = y.ndim
    for step in group:
        if step.method not in ("eig", "als"):
            raise ValueError(
                f"method {step.method!r} cannot run in a mode-parallel "
                "group (plan-time resolution should have rejected it)")
    shard = group[0].shard_mode   # one shard mode serves the whole group
    y = _reshard(y, mesh, shard, axis)
    factors: dict[int, jax.Array] = {}
    if shard is None:
        # replicated group (it covered every shardable mode): plain local
        # Grams / ALS on the full tensor, then the fused truncation chain
        for step in group:
            if step.method == "eig":
                factors[step.mode] = _eig_u(T.gram(y, step.mode),
                                            step.r_n, y.dtype)
            else:
                u, _ = als_solve(y, step.mode, step.r_n,
                                 num_iters=als_iters)
                factors[step.mode] = u
        y_new = y
        for step in group:
            y_new = T.ttm(y_new, factors[step.mode].T, step.mode)
        return factors, y_new
    eig_steps = [s for s in group if s.method == "eig"]
    if eig_steps:
        modes = tuple(s.mode for s in eig_steps)
        grams = _gram_group_psum(mesh, axis, n, modes, shard)(y)
        for step, s in zip(eig_steps, grams):
            factors[step.mode] = _eig_u(s, step.r_n, y.dtype)
    for step in group:
        if step.method == "als":
            # GSPMD from the shared (still un-shrunk) input; the eager
            # y_new it also produces is unused and DCE'd under jit
            u, _ = _als_gspmd(y, step, mesh, axis, als_iters=als_iters)
            factors[step.mode] = u
    modes_all = tuple(s.mode for s in group)
    uts = tuple(factors[m].T for m in modes_all)
    y = _ttm_group_local(mesh, axis, n, modes_all, shard)(y, *uts)
    return factors, y


def run_sharded_schedule(x: jax.Array, steps, mesh: Mesh, axis: str, *,
                         als_iters: int = DEFAULT_ALS_ITERS,
                         block_until_ready: bool = True):
    """Eager runner: per-step execution with real wall-clock per mode.

    Mode-parallel groups run as one unit; their wall-clock is attributed
    evenly across the members so ``seconds`` stays index-aligned with
    ``steps``.  Returns ``(y, factors, seconds)`` like
    :func:`repro.core.plan.run_schedule` (``factors`` keyed by mode).
    """
    from .plan import iter_groups
    y = x
    factors: dict[int, jax.Array] = {}
    seconds: list[float] = []
    platform = jax.default_backend()
    for batch in iter_groups(steps):
        wall0 = time.time()
        t0 = time.perf_counter()
        if len(batch) == 1:
            u, y = solve_step_sharded(y, batch[0], mesh, axis,
                                      als_iters=als_iters)
            factors[batch[0].mode] = u
        else:
            fs, y = solve_group_sharded(y, batch, mesh, axis,
                                        als_iters=als_iters)
            factors.update(fs)
        if block_until_ready:
            jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        seconds.extend([dt / len(batch)] * len(batch))
        if block_until_ready:
            for s in batch:
                # group wall-clock attributed evenly, matching ``seconds``
                _obs.event("span", t=wall0, name="solve",
                           dur_s=dt / len(batch), mode=s.mode,
                           solver=s.method, backend="sharded",
                           platform=platform, rank=s.r_n, i_n=s.i_n,
                           j_n=s.j_n, n_shards=s.n_shards,
                           group=s.group, predicted_s=s.predicted_s)
                _drift.MONITOR.observe(platform=platform, backend="sharded",
                                       solver=s.method,
                                       predicted_s=s.predicted_s,
                                       actual_s=dt / len(batch),
                                       source="execute")
    return y, factors, seconds


def sweep_sharded(x, steps, *, mesh: Mesh, axis: str, als_iters: int):
    """The whole sharded sweep as one pure function, jit-compiled by
    ``TuckerPlan`` — inner shard_maps and sharding constraints inline into a
    single XLA program with the reshard collectives at the frozen points."""
    y = x
    factors: dict[int, jax.Array] = {}
    for step in steps:
        u, y = solve_step_sharded(y, step, mesh, axis, als_iters=als_iters)
        factors[step.mode] = u
    return y, [factors[m] for m in range(x.ndim)]


def sweep_mode_parallel(x, steps, *, mesh: Mesh, axis: str, als_iters: int):
    """Group-aware whole-sweep: like :func:`sweep_sharded` but schedules
    carrying ``group`` ids run each group through
    :func:`solve_group_sharded` (concurrent Grams, one fused multi-TTM).
    Pure — compiled by the same ``TuckerPlan`` sweep cache, so repeated
    execution of a mode-parallel plan stays zero-recompile."""
    from .plan import iter_groups
    y = x
    factors: dict[int, jax.Array] = {}
    for batch in iter_groups(steps):
        if len(batch) == 1:
            u, y = solve_step_sharded(y, batch[0], mesh, axis,
                                      als_iters=als_iters)
            factors[batch[0].mode] = u
        else:
            fs, y = solve_group_sharded(y, batch, mesh, axis,
                                        als_iters=als_iters)
            factors.update(fs)
    return y, [factors[m] for m in range(x.ndim)]


# ---------------------------------------------------------------------------
# Legacy entry point — thin wrapper over the shared schedule machinery
# ---------------------------------------------------------------------------

def sthosvd_distributed(
    x: jax.Array,
    ranks,
    mesh: Mesh,
    *,
    axis: str = "data",
    methods: str = "eig",
    als_iters: int = DEFAULT_ALS_ITERS,
    selector=None,
    mode_order=None,
    memory_cap_bytes: int | None = None,
    mode_parallel: str | int = "off",
    block_until_ready: bool = True,
) -> SthosvdResult:
    """Distributed flexible st-HOSVD.  ``methods``: 'eig' | 'als' | 'auto'.

    ``mode_order="opt"`` runs the subset-DP schedule search against the
    PER-DEVICE peak model (shard participation per state follows
    :func:`pick_shard_mode`); ``memory_cap_bytes`` is the per-device cap —
    the regime where sharding decides whether a mode fits at all.
    ``mode_parallel`` ("off" | "auto" | int) opts steps into concurrent
    mode-parallel groups — see :func:`repro.core.plan.resolve_schedule`.

    Thin wrapper over the shared plan machinery: the per-mode solver AND
    shard-mode schedule is resolved ahead of time
    (:func:`repro.core.plan.resolve_schedule` with ``backend="sharded"``),
    then run eagerly with real per-mode wall-clock in the trace — exactly
    how :func:`repro.core.sthosvd.sthosvd` wraps the single-device runner.
    For amortized/batched execution build a plan instead:
    ``plan(shape, dtype, TuckerConfig(..., impl="sharded", mesh=mesh))``.
    """
    from .plan import TimedSelector, resolve_schedule

    timed = None
    if methods == "auto":
        if selector is None:
            from .selector import default_selector
            selector = default_selector()
        selector = timed = TimedSelector(selector)
    schedule = resolve_schedule(
        x.shape, ranks, variant="sthosvd", methods=methods, selector=selector,
        mode_order=mode_order, als_iters=als_iters,
        itemsize=x.dtype.itemsize, backend="sharded",
        n_shards=mesh.shape[axis], memory_cap_bytes=memory_cap_bytes,
        mode_parallel=mode_parallel)

    y, factors, seconds = run_sharded_schedule(
        x, schedule, mesh, axis, als_iters=als_iters,
        block_until_ready=block_until_ready)
    trace = [ModeTrace(s.mode, s.method, s.i_n, s.r_n, s.j_n, dt,
                       backend=s.backend, predicted_s=s.predicted_s)
             for s, dt in zip(schedule, seconds)]
    tucker = TuckerTensor(core=y, factors=[factors[m] for m in range(x.ndim)])
    return SthosvdResult(tucker=tucker, trace=trace,
                         select_overhead_s=timed.seconds if timed else 0.0)
