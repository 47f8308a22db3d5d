"""Pluggable ops backends: who actually computes TTM / Gram / TTT.

The paper separates *what* to solve per mode (the adaptive EIG/ALS/SVD
schedule, Sec. III–IV) from *how* the three tensor primitives run on the
hardware (the matricization-free CPU/GPU kernels, Sec. V).  This module is
that seam for the JAX port: an :class:`OpsBackend` bundles the three
primitives with capability metadata, and a process-wide registry maps names
to backends so every layer — solvers, schedules, plans, the serving engine,
benchmarks — routes through one dispatch point instead of pattern-matching
an ``impl`` string.

Built-in backends:

  ``matfree``   one ``lax.dot_general`` per primitive over the tensor's own
                axes — no unfold and no merged view (tensor_ops).
  ``explicit``  unfold → GEMM → fold baseline (paper Fig. 3 / Fig. 8).
  ``pallas``    hand-written Pallas TPU kernels (kernels/ops.py): tiled
                matmul / batched-TTM / TTT that mask ragged edge blocks
                in-kernel (no padded copies, so the plan's peak model
                holds as is); interpret-mode fallback off-TPU so the same
                code path runs (slowly) everywhere.
  ``sharded``   multi-device st-HOSVD over a jax mesh (core/distributed.py):
                TuckerMPI-style partial-Gram + psum and local TTM under
                shard_map, resharding to the largest remaining mode between
                steps.  Requires a mesh (``TuckerConfig(mesh=...)``); the
                local per-device primitives are ``matfree``'s, so this
                backend never matricizes either.

``resolve_backend("auto", ...)`` picks the best available backend for the
current platform at *plan* time (a mesh → ``sharded``, TPU → ``pallas``,
otherwise ``matfree``), honouring each backend's dtype/platform
capabilities.  Custom backends register via :func:`register_backend` and
are immediately usable as ``impl=`` values.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from . import tensor_ops as T

#: Ops signature: ttm(x, u, mode) with u (R, I_n); gram(x, mode) → (I_n, I_n);
#: ttt(x, y, mode) → (I_n, R_n).  All dispatch positionally so backends with
#: extra keyword knobs (precision, interpret, …) plug in unchanged.
OpsTriple = tuple[Callable, Callable, Callable]


@dataclass(frozen=True)
class OpsBackend:
    """One named implementation of the three mode-n primitives.

    ``loader`` defers the import of heavyweight kernel modules until the
    backend is first used; the resolved triple is cached on the instance.

    Capability metadata drives ``auto`` resolution and plan-time validation:

    dtypes
        dtype names the primitives accept (``"*"`` = anything jnp takes).
    platforms
        jax backend names this runs *natively* on (``"*"`` = any).  A
        backend with ``interpret_fallback=True`` additionally runs anywhere
        through the Pallas interpreter — correct but slow, for testing.
    matricizes
        True if the primitives materialize mode-n unfoldings (extra
        O(I_n·J_n) buffer; the paper's Fig. 8 memory axis).  Note the SVD
        *solver* unfolds regardless of backend — see
        :func:`repro.core.solvers.svd_solve`.
    cost_scale
        Relative per-FLOP cost hint vs ``matfree`` on this backend's native
        platform; the selector/cost model may scale Eq. 4/5 estimates by it.
    native_axes
        True if the primitives contract over the tensor's own axes and
        take it as it is.  False: they reshape to merged ``(A, I_n, B)``
        views, which on a tiled TPU layout copies the tensor, so a solver
        that contracts one tensor many times makes that view once itself
        (:func:`repro.core.solvers.als_solve`).
    requires_mesh
        True if the backend executes across a jax mesh: plans must carry one
        (``TuckerConfig(mesh=...)``), ``auto`` only selects it when a mesh is
        supplied, and per-step ``peak_bytes`` become per-device figures.
    solvers
        Solver families (``repro.core.solvers.SOLVERS`` names) whose kernel
        mix this backend supports.  All four built-ins support the full set
        — ``rand`` is built from the same TTM/TTT/Gram primitives — but a
        custom backend that e.g. lacks a TTT can exclude ``als``/``rand``
        here and plan-time validation (``plan._make_step``) rejects the
        combination before anything compiles.
    """
    name: str
    loader: Callable[[], OpsTriple]
    dtypes: tuple[str, ...] = ("*",)
    platforms: tuple[str, ...] = ("*",)
    matricizes: bool = False
    cost_scale: float = 1.0
    interpret_fallback: bool = False
    native_axes: bool = False
    requires_mesh: bool = False
    solvers: tuple[str, ...] = ("eig", "als", "svd", "rand")
    _ops: list = field(default_factory=list, repr=False, compare=False)

    def ops(self) -> OpsTriple:
        """Resolve (ttm, gram, ttt), importing lazily on first use."""
        if not self._ops:
            self._ops.append(self.loader())
        return self._ops[0]

    def supports_dtype(self, dtype) -> bool:
        return "*" in self.dtypes or str(jnp.dtype(dtype)) in self.dtypes

    def supports_solver(self, method: str) -> bool:
        return "*" in self.solvers or method in self.solvers

    def native_on(self, platform: str) -> bool:
        return "*" in self.platforms or platform in self.platforms


_REGISTRY: dict[str, OpsBackend] = {}


def register_backend(backend: OpsBackend, *, overwrite: bool = False) -> OpsBackend:
    """Add ``backend`` to the registry (its name becomes a valid ``impl=``)."""
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered "
                         "(pass overwrite=True to replace)")
    if backend.name == "auto":
        raise ValueError("'auto' is reserved for plan-time resolution")
    _REGISTRY[backend.name] = backend
    return backend


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def backend_names() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def get_backend(name: str) -> OpsBackend:
    """Look up a backend by name; raises ValueError listing known names."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{backend_names()} (or 'auto')") from None


#: ``auto`` preference order per platform: first registered name that is
#: native on the platform and supports the dtype wins.
AUTO_ORDER: dict[str, tuple[str, ...]] = {
    "tpu": ("pallas", "matfree"),
    "gpu": ("matfree",),
    "cpu": ("matfree",),
}


def resolve_backend(impl: str, *, platform: str | None = None,
                    dtype=None, mesh=None) -> OpsBackend:
    """Resolve an ``impl`` name (or ``"auto"``) to a concrete backend.

    Explicit names are honoured even off their native platform when the
    backend has an interpreter/emulation path (``pallas`` off-TPU runs in
    Pallas interpret mode) — asking for a backend by name means you want
    *that* code path.  ``"auto"`` only ever picks natively-supported
    backends, falling back to ``matfree``; when ``mesh`` (a
    ``jax.sharding.Mesh``) is supplied, ``auto`` routes to the ``sharded``
    mesh backend so plans built with a mesh execute distributed by default.
    """
    platform = platform or jax.default_backend()
    if impl != "auto":
        b = get_backend(impl)
        if dtype is not None and not b.supports_dtype(dtype):
            raise ValueError(f"backend {b.name!r} does not support dtype "
                             f"{jnp.dtype(dtype)} (supported: {b.dtypes})")
        if b.requires_mesh and mesh is None:
            raise ValueError(f"backend {b.name!r} requires a mesh; pass "
                             "TuckerConfig(mesh=...) or call "
                             "sthosvd_distributed directly")
        if not b.native_on(platform) and not b.interpret_fallback:
            raise ValueError(f"backend {b.name!r} runs on {b.platforms}, not "
                             f"{platform!r}, and has no interpreter fallback")
        return b
    if mesh is not None and "sharded" in _REGISTRY:
        b = _REGISTRY["sharded"]
        if dtype is None or b.supports_dtype(dtype):
            return b
    for name in AUTO_ORDER.get(platform, ("matfree",)):
        b = _REGISTRY.get(name)
        if b is not None and b.native_on(platform) and \
                (dtype is None or b.supports_dtype(dtype)):
            return b
    return get_backend("matfree")


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------

def _load_matfree() -> OpsTriple:
    return T.ttm, T.gram, T.ttt


def _load_explicit() -> OpsTriple:
    return T.ttm_explicit, T.gram_explicit, T.ttt_explicit


def _load_pallas() -> OpsTriple:
    """kernels/ops.py with dtype adapters matching matfree's contract.

    The Pallas kernels accumulate and return fp32; matfree keeps the input
    dtype for TTM and promotes to (at least) fp32 for Gram/TTT.  The
    adapters restore that contract so sweeps thread dtypes identically
    across backends (a bf16 plan shrinks a bf16 tensor either way).
    """
    from ..kernels import ops as K

    def ttm(x, u, mode):
        return K.ttm(x, u, mode).astype(x.dtype)

    def gram(x, mode):
        return K.gram(x, mode).astype(jnp.promote_types(x.dtype, jnp.float32))

    def ttt(x, y, mode):
        return K.ttt(x, y, mode).astype(jnp.promote_types(x.dtype, jnp.float32))

    return ttm, gram, ttt


register_backend(OpsBackend(
    name="matfree", loader=_load_matfree,
    dtypes=("*",), platforms=("*",), matricizes=False, native_axes=True,
    cost_scale=1.0))

register_backend(OpsBackend(
    name="explicit", loader=_load_explicit,
    dtypes=("*",), platforms=("*",), matricizes=True,
    # the unfold copy is pure overhead; Fig. 8's explicit rows pay it
    cost_scale=1.3))

register_backend(OpsBackend(
    name="pallas", loader=_load_pallas,
    # fp64 has no Mosaic tile mapping; fp32/bf16 are what the kernels tile
    dtypes=("float32", "bfloat16"), platforms=("tpu",),
    matricizes=False,
    # hand-tiled MXU kernels: modestly better than XLA's generic batched GEMM
    cost_scale=0.9,
    # kernels/ops.py defaults interpret=True off-TPU, so explicit
    # `impl="pallas"` works — slowly — on any platform
    interpret_fallback=True))

register_backend(OpsBackend(
    # the shard_map schedule runs matfree's primitives per device; mesh
    # plumbing (partial-Gram psum, local TTM, resharding) lives in
    # core/distributed.py and is wired in by the plan layer
    name="sharded", loader=_load_matfree,
    dtypes=("*",), platforms=("*",), matricizes=False, native_axes=True,
    requires_mesh=True, cost_scale=1.0))


def backend_ops(impl: str) -> OpsTriple:
    """(ttm, gram, ttt) for a registered backend name — the solver hot path."""
    return get_backend(impl).ops()
