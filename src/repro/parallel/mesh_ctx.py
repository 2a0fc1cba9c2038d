"""Global mesh context + sharding-constraint helpers.

Models are written mesh-agnostic: they call ``constrain(x, *axes)`` with
*logical* axis names; if no mesh is active (unit tests, smoke tests on one
CPU device) the call is a no-op.  When a mesh is active, logical axes are
resolved against it with two safety rules:

  * axis names missing from the mesh are dropped (e.g. "pod" on the
    single-pod mesh);
  * axes that do not divide the dimension are dropped (replicate instead) —
    this implements the "auto" head-vs-sequence attention TP selection and
    makes every arch (9-head smollm, 40-head phi3, ...) lower cleanly.

Axis conventions: "pod" (inter-pod DP), "data" (DP / context parallel),
"model" (TP / EP).  A logical axis may be a tuple, e.g. ("data", "model")
shards one dim over both.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# Logical batch axis = all DP axes that exist in the mesh.
BATCH_AXES = ("pod", "data")


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def axis_size(name: str) -> int:
    """Size of a mesh axis; 1 if absent or no mesh."""
    mesh = current_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def dp_size() -> int:
    return axis_size("pod") * axis_size("data")


def tp_size() -> int:
    return axis_size("model")


def _resolve_entry(entry, dim: int, mesh: Mesh):
    """Resolve one PartitionSpec entry against the mesh + divisibility."""
    if entry is None:
        return None
    names = entry if isinstance(entry, tuple) else (entry,)
    kept = []
    prod = 1
    for nm in names:
        if nm in mesh.axis_names and dim % (prod * mesh.shape[nm]) == 0:
            kept.append(nm)
            prod *= mesh.shape[nm]
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


def resolve_spec(shape, spec: P) -> P:
    """Sanitise a PartitionSpec for the current mesh (see module docstring)."""
    mesh = current_mesh()
    if mesh is None:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = [_resolve_entry(e, d, mesh) for e, d in zip(entries, shape)]
    return P(*out)


def constrain(x, *spec_entries):
    """with_sharding_constraint with logical axes; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(x.shape, P(*spec_entries))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(shape, spec: P) -> NamedSharding | None:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve_spec(shape, spec))


def mesh_axes(mesh: Mesh, axis=None) -> tuple:
    """Normalise an axis selection to a tuple of mesh axis names.

    ``axis=None`` selects ALL axes of the mesh (shard over every device);
    a string selects one axis; a tuple passes through.  Unknown names raise.
    """
    if axis is None:
        return tuple(mesh.axis_names)
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    for nm in names:
        if nm not in mesh.axis_names:
            raise ValueError(f"axis {nm!r} not in mesh axes {mesh.axis_names}")
    return names


def mesh_axes_size(mesh: Mesh, axes: tuple) -> int:
    """Number of devices along ``axes`` (their product)."""
    size = 1
    for nm in axes:
        size *= mesh.shape[nm]
    return size
