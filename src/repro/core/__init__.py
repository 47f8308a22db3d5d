"""a-Tucker core: input-adaptive, matricization-free Tucker decomposition.

Public API:
  TuckerConfig / plan / TuckerPlan / decompose — plan/execute front door
      (static solver schedules, cached jitted sweeps, batched execution)
  sthosvd / sthosvd_eig / sthosvd_als / sthosvd_svd — flexible st-HOSVD
      (legacy per-call wrappers over the same schedule runner)
  TuckerTensor — decomposition result (reconstruct, rel_error, ratio)
  Selector / default_selector — adaptive solver selector, resolved per
      (platform, backend); trained/calibrated by the repro.tune flywheel
  CostModel — Eq. 4/5 constants (textbook default, hardware-calibratable)
  tensor_ops — matricization-free TTM/TTT/Gram (+ explicit baselines)
  OpsBackend / register_backend / get_backend / resolve_backend /
      backend_names — pluggable ops-backend registry (matfree | explicit |
      pallas | sharded | custom) behind TuckerConfig.impl
  distributed — mesh execution engine behind the ``sharded`` backend
      (sthosvd_distributed legacy entry, pick_shard_mode, shard_map sweeps)
"""

# NOTE: the attribute ``repro.core.plan`` is the api.plan FUNCTION (the
# front-door entry point), which shadows the ``plan`` submodule on the
# package.  ``from repro.core.plan import ...`` still resolves the module
# (sys.modules), and ``plan_lib`` aliases it for attribute-style access.
from . import backend, cost_model, plan as plan_lib, tensor_ops, variants
from .api import (
    TuckerConfig,
    TuckerPlan,
    decompose,
    mesh_from_spec,
    mesh_spec,
    plan,
)
from .backend import (
    OpsBackend,
    backend_names,
    get_backend,
    register_backend,
    resolve_backend,
)
from .cost_model import DEFAULT_COST_MODEL, CostModel
from .errors import (CancelledError, DeadlineError, InputError, KernelError,
                     NumericalError, ResourceError, TuckerError,
                     check_finite, classify_exception, coerce_exception)
from .plan import ModeStep, resolve_schedule
from .schedule_opt import (MemoryCapError, ScheduleSearch,
                           optimize_grouping, optimize_schedule)
from .selector import Selector, default_selector, extract_features
from .solvers import (ALS, EIG, RAND, SVD, als_solve, eig_solve, rand_sketch,
                      rand_solve, svd_solve)
from .sthosvd import (
    SthosvdResult,
    TuckerTensor,
    sthosvd,
    sthosvd_als,
    sthosvd_eig,
    sthosvd_svd,
)

__all__ = [
    "ALS", "DEFAULT_COST_MODEL", "EIG", "RAND", "SVD",
    "CancelledError", "CostModel", "DeadlineError", "InputError", "KernelError",
    "MemoryCapError", "ModeStep", "NumericalError", "OpsBackend",
    "ResourceError", "ScheduleSearch", "Selector", "SthosvdResult",
    "TuckerConfig", "TuckerError", "TuckerPlan", "TuckerTensor",
    "als_solve", "backend", "backend_names", "check_finite",
    "classify_exception", "coerce_exception", "cost_model", "decompose",
    "default_selector", "eig_solve", "extract_features", "get_backend",
    "mesh_from_spec", "mesh_spec", "optimize_grouping",
    "optimize_schedule", "plan", "plan_lib",
    "rand_sketch", "rand_solve",
    "register_backend", "resolve_backend", "resolve_schedule", "sthosvd",
    "sthosvd_als", "sthosvd_eig", "sthosvd_svd", "svd_solve", "tensor_ops",
    "variants",
]
