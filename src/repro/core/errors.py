"""Failure taxonomy for the a-Tucker stack: every failure classified.

The execution layers (``plan.execute``, the serve waves, the eager
runners) raise — or wrap foreign exceptions into — one hierarchy rooted at
:class:`TuckerError`, so callers can catch by failure CLASS instead of
pattern-matching XLA message strings:

  * :class:`InputError`       — the caller's tensor/config is bad (NaN/Inf
    inputs, shape/dtype mismatch).  Subclasses ``ValueError``.
  * :class:`NumericalError`   — the computation broke down (Cholesky
    failure in ALS, non-finite solver outputs).  Subclasses
    ``FloatingPointError``.
  * :class:`ResourceError`    — the runtime ran out of something (XLA
    ``RESOURCE_EXHAUSTED`` / OOM, a dead or abandoned worker).
  * :class:`KernelError`      — a Pallas kernel failed to lower or compile
    for the platform (a Mosaic error).  Never degraded onto another
    backend: a run that asked for the kernels must not pass without them.
  * :class:`DeadlineError`    — a serve request missed its ``deadline_s``
    before dispatch.  Subclasses ``TimeoutError``.
  * :class:`CancelledError`   — the caller retracted the request via
    ``TuckerService.cancel``.

:func:`classify_exception` maps raw JAX/XLA exceptions onto the taxonomy
(`None` when it cannot — programming errors stay themselves), and
:func:`coerce_exception` always returns a ``TuckerError`` (wrapping
unclassifiable failures in the base class) — the serve layer's guarantee
that no unclassified exception escapes to a caller.  The subclassing of
the matching builtins keeps every pre-taxonomy ``except ValueError`` /
``except TimeoutError`` call site working unchanged.

The execute-time fallback ladder (see ``TuckerPlan.execute``) keys its
hops off these classes: rand→eig on a sketch error-target miss, als→eig
(then pallas→matfree) on :class:`NumericalError`,
donated→undonated→replanned-under-a-tighter-cap on
:class:`ResourceError`.  :class:`KernelError` and unclassified failures
raise without a hop.
"""

from __future__ import annotations

__all__ = [
    "CancelledError", "DeadlineError", "InputError", "KernelError",
    "NumericalError",
    "ResourceError", "TuckerError", "check_finite", "check_result_finite",
    "classify_exception", "coerce_exception",
]


class TuckerError(RuntimeError):
    """Base of the classified-failure hierarchy (see module docstring)."""


class InputError(TuckerError, ValueError):
    """The caller's input is unusable: non-finite entries, or a tensor that
    does not match the plan's shape/dtype.  ``mode`` names the tensor mode
    whose slices concentrate the corruption (None when not applicable)."""

    def __init__(self, message: str, *, mode: int | None = None):
        super().__init__(message)
        self.mode = mode


class NumericalError(TuckerError, FloatingPointError):
    """The computation broke down numerically: a Cholesky factorization
    failed past its re-regularization ladder, or a solver produced
    non-finite factors from a finite input."""


class ResourceError(TuckerError):
    """The runtime ran out of a resource: XLA ``RESOURCE_EXHAUSTED``/OOM,
    an allocation failure, or a serve worker that died/was abandoned."""


class KernelError(TuckerError):
    """A Pallas kernel failed to lower or to compile (Mosaic) for the
    platform it was asked to run on."""


class DeadlineError(TuckerError, TimeoutError):
    """A served request's ``deadline_s`` expired before it was dispatched
    (checked at admission and again at wave formation)."""


class CancelledError(TuckerError):
    """The request was retracted via ``TuckerService.cancel`` before it
    was dispatched."""


#: message fragments that mark an XLA/runtime allocation failure
_RESOURCE_MARKERS = (
    "RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED", "Out of memory",
    "out of memory", "OOM", "failed to allocate", "Failed to allocate",
    "Resource exhausted",
)
#: exception types and message fragments that mark a kernel that failed to
#: lower (Pallas) or to compile (Mosaic); checked before the resource
#: markers, since a kernel over its VMEM budget also reports exhaustion
_KERNEL_TYPES = ("LoweringException", "MosaicError")
_KERNEL_MARKERS = (
    "Mosaic", "Only interpret mode is supported",
)
#: message fragments that mark a numerical breakdown
_NUMERICAL_MARKERS = (
    "Cholesky", "cholesky", "not positive definite", "non-finite",
    "not finite", "NaN", "nan produced", "singular matrix",
    "did not converge",
)


def classify_exception(exc: BaseException) -> TuckerError | None:
    """Map a raw exception onto the taxonomy, or None when it defies
    classification (shape errors, programming bugs — those should stay
    themselves).  Already-classified errors pass through unchanged; a
    fresh wrapper chains the original via ``__cause__``."""
    if isinstance(exc, TuckerError):
        return exc
    msg = str(exc)
    wrapped: TuckerError | None = None
    if type(exc).__name__ in _KERNEL_TYPES or \
            any(m in msg for m in _KERNEL_MARKERS):
        wrapped = KernelError(f"kernel failed to lower or compile: {msg}")
    elif isinstance(exc, MemoryError) or \
            any(m in msg for m in _RESOURCE_MARKERS):
        wrapped = ResourceError(f"resource exhausted: {msg}")
    elif isinstance(exc, (FloatingPointError, ZeroDivisionError)) or \
            any(m in msg for m in _NUMERICAL_MARKERS):
        wrapped = NumericalError(f"numerical breakdown: {msg}")
    if wrapped is not None:
        wrapped.__cause__ = exc
    return wrapped


def coerce_exception(exc: BaseException) -> TuckerError:
    """Like :func:`classify_exception`, but total: unclassifiable failures
    come back wrapped in the :class:`TuckerError` base (original chained
    via ``__cause__``) — the serve layer's no-unclassified-escapes
    guarantee."""
    t = classify_exception(exc)
    if t is None:
        t = TuckerError(f"unclassified failure: {exc!r}")
        t.__cause__ = exc
    return t


def check_finite(x, *, name: str = "input") -> None:
    """Raise :class:`InputError` when ``x`` holds NaN/Inf, naming the
    tensor mode whose slices concentrate the corruption (the diagnosis
    walk runs only on the failure path; the pass path is one fused
    ``isfinite`` reduction)."""
    import jax.numpy as jnp
    finite = jnp.isfinite(x)
    if bool(jnp.all(finite)):
        return
    bad = jnp.logical_not(finite)
    n_bad = int(jnp.sum(bad))
    ndim = getattr(x, "ndim", 0)
    if ndim == 0:
        raise InputError(f"{name} is non-finite ({float(x)!r})")
    worst = (0, 0, -1)   # (mode, slice index, bad count in that slice)
    for mode in range(ndim):
        axes = tuple(a for a in range(ndim) if a != mode)
        per_slice = jnp.sum(bad, axis=axes) if axes else bad.astype(jnp.int32)
        idx = int(jnp.argmax(per_slice))
        cnt = int(per_slice[idx])
        if cnt > worst[2]:
            worst = (mode, idx, cnt)
    mode, idx, cnt = worst
    raise InputError(
        f"{name} contains {n_bad} non-finite value(s); the worst "
        f"concentration is mode {mode} (slice {idx} holds {cnt} of them)",
        mode=mode)


def check_result_finite(core, factors, *, context: str = "sweep") -> None:
    """Raise :class:`NumericalError` when a solve's outputs carry NaN/Inf
    (the post-execution guard of the fused ``validate="finite"`` path and
    the serve layer's lane quarantine)."""
    import jax.numpy as jnp
    if not bool(jnp.all(jnp.isfinite(core))):
        raise NumericalError(
            f"{context} produced a non-finite core tensor")
    for m, u in enumerate(factors):
        if not bool(jnp.all(jnp.isfinite(u))):
            raise NumericalError(
                f"{context} produced a non-finite mode-{m} factor")
