"""Param / activation sharding rules with divisibility-checked fallbacks.

MaxText-style logical rules resolved against a concrete mesh:
  * tensor-parallel ('model') axis: vocab dim of embeddings, the d_ff /
    heads output dim of up-projections, the contraction dim of
    down-projections — picked by key-name pattern on the param path.
  * FSDP ('data' axis, optionally 'pod' too) on the largest remaining dim
    for configs flagged ``fsdp`` (the ≥7B archs).
  * every assignment is dropped silently when the dim doesn't divide the
    mesh axis (e.g. gemma3's 4 query heads vs model=16 → that dim stays
    replicated and d_ff carries the TP).

The resolver works on abstract (ShapeDtypeStruct) pytrees so the dry-run
never allocates.
"""

from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .config import ModelConfig

# (path-regex, dim-from-end, logical-role). First match wins per dim.
# dims are indexed from the END so stacked (leading L / E) axes don't shift
# the rule.
_TP_LAST = ("wq", "wk", "wv", "wi_gate", "wi_up", "wi", "in_proj", "bc_proj",
            "dt_proj", "w_gate", "w_up", "conv_w", "d_skip", "dt_bias")
_TP_SECOND = ("wo", "out_proj", "w_down", "x_proj", "a_log")
_EMBED = ("embed", "lm_head")


def _leaf_spec(path: str, shape: tuple[int, ...], mesh: Mesh, *,
               model_axis: str, fsdp_axes: tuple[str, ...] | None) -> P:
    parts: list[Any] = [None] * len(shape)
    name = path.rsplit("/", 1)[-1]

    def try_assign(dim_from_end: int, axis):
        i = len(shape) - dim_from_end
        if i < 0 or parts[i] is not None:
            return False
        size = _axes_size(mesh, axis)
        if shape[i] % size == 0 and shape[i] >= size:
            parts[i] = axis
            return True
        return False

    if name in _EMBED:
        # (V, d) or (d, V): shard the vocab dim
        vdim = 0 if shape[-2] >= shape[-1] else 1
        try_assign(2 - vdim, model_axis)
    elif name in _TP_LAST:
        try_assign(1, model_axis)
    elif name in _TP_SECOND:
        try_assign(2, model_axis)
    # norms / scalars / router: replicated for TP

    if fsdp_axes:
        # largest remaining dim takes the data axes (zero-redundancy style)
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if parts[i] is None and shape[i] % _axes_size(mesh, fsdp_axes) == 0 \
                    and shape[i] >= _axes_size(mesh, fsdp_axes):
                parts[i] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
                break

    return P(*parts)


def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.  The model code shards through
    GSPMD sharding constraints, which the ``Explicit`` axes that
    ``jax.make_mesh`` builds by default refuse."""
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _axes_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def param_specs(params_abs: Any, mesh: Mesh, *, model_axis: str = "model",
                fsdp_axes: tuple[str, ...] | None = None,
                replicate_names: tuple[str, ...] = ()) -> Any:
    """PartitionSpec pytree for a (possibly abstract) param pytree.
    ``replicate_names``: leaf names exempted from TP (e.g. expert weights
    under capacity sharding)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_abs)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if pstr.rsplit("/", 1)[-1] in replicate_names:
            specs.append(P(*([None] * leaf.ndim)))
            continue
        specs.append(_leaf_spec(pstr, tuple(leaf.shape), mesh,
                                model_axis=model_axis, fsdp_axes=fsdp_axes))
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params_abs: Any, mesh: Mesh, **kw) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_abs, mesh, **kw))


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------

def batch_specs(batch_abs: Any, mesh: Mesh, *, batch_axes=("pod", "data")) -> Any:
    """Shard the leading (batch) dim of every input over the data axes; fall
    back to sequence sharding when batch doesn't divide (long-context B=1)."""
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    size = _axes_size(mesh, axes)

    def leaf(x):
        if x.ndim == 0:
            return P()
        if x.shape[0] % size == 0 and x.shape[0] >= size:
            return P(axes)
        if x.ndim >= 2 and x.shape[1] % size == 0:
            return P(None, axes)          # sequence sharding
        return P()

    return jax.tree.map(leaf, batch_abs)


def cache_specs_tree(cache_abs: Any, mesh: Mesh, *, batch_axes=("pod", "data")) -> Any:
    """KV/SSM caches are stacked (L, B, S, ...): shard batch; for B=1
    long-context, shard the sequence dim (ring-attention style residency)."""
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    size = _axes_size(mesh, axes)

    def leaf(x):
        parts = [None] * x.ndim
        if x.ndim >= 2 and x.shape[1] % size == 0 and x.shape[1] >= size:
            parts[1] = axes
        elif x.ndim >= 3 and x.shape[2] % size == 0:
            parts[2] = axes               # sequence dim of (L, B, S, …)
        return P(*parts)

    return jax.tree.map(leaf, cache_abs)


def to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s, spec_tree,
        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# activation-sharding context: model code calls ``constrain_batch`` on its
# (B, T, d) activations; outside a mesh context it's a no-op, so CPU tests
# never notice.  The dry-run / trainer set the axes once per process.
# ---------------------------------------------------------------------------

_ACT_MESH: Mesh | None = None
_ACT_BATCH_AXES: tuple[str, ...] = ("pod", "data")


def set_activation_mesh(mesh: Mesh | None, axes: tuple[str, ...] = ("pod", "data")):
    global _ACT_MESH, _ACT_BATCH_AXES
    _ACT_MESH = None if mesh is None else auto_mesh(mesh)
    _ACT_BATCH_AXES = tuple(axes)


def activation_mesh() -> Mesh | None:
    return _ACT_MESH


def constrain_batch(x):
    """Constrain dim 0 of an activation to the data axes (if a mesh was
    registered and the dim divides); identity otherwise — CPU tests never
    notice."""
    if _ACT_MESH is None:
        return x
    axes = tuple(a for a in _ACT_BATCH_AXES if a in _ACT_MESH.shape)
    if not axes:
        return x
    size = 1
    for a in axes:
        size *= _ACT_MESH.shape[a]
    if x.ndim == 0 or x.shape[0] % size or x.shape[0] < size:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_ACT_MESH, P(axes)))


def constrain(x, parts: tuple):
    """Constrain ``x`` to PartitionSpec(parts) on the registered mesh, with
    per-dim divisibility fallback (dims that don't divide stay unsharded).
    Axis entries may be tuples of mesh axes (e.g. ("pod", "data"))."""
    if _ACT_MESH is None:
        return x
    resolved = []
    for dim, axis in enumerate(parts):
        if axis is None:
            resolved.append(None)
            continue
        axes = tuple(a for a in (axis if isinstance(axis, tuple) else (axis,))
                     if a in _ACT_MESH.shape)
        if not axes:
            resolved.append(None)
            continue
        size = _axes_size(_ACT_MESH, axes)
        ok = dim < x.ndim and x.shape[dim] % size == 0 and x.shape[dim] >= size
        resolved.append((axes if len(axes) > 1 else axes[0]) if ok else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_ACT_MESH, P(*resolved)))
