"""Sharding-constraint helpers usable from model code.

``constrain(x, spec)`` applies ``with_sharding_constraint`` against the
*ambient* mesh (the ``with mesh:`` context the launcher establishes) and is
a no-op when there is no mesh (unit tests, host examples) or when a named
axis does not divide the corresponding dim.  This keeps model code
mesh-agnostic while letting us pin down activation layouts where GSPMD's
propagation picks pathological strategies (e.g. partially-sharded attention
contractions when head counts don't divide the model axis).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# The launcher-registered mesh (``with mesh:`` does not populate JAX's
# abstract-mesh context in this version, so we carry our own).
_ACTIVE_MESH = None


def set_active_mesh(mesh) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


class active_mesh:
    """Context manager: ``with active_mesh(mesh): fn.lower(...)``"""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        self.prev = _ACTIVE_MESH
        set_active_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        set_active_mesh(self.prev)
        return False


def _ambient_mesh():
    if _ACTIVE_MESH is not None:
        return _ACTIVE_MESH
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return None
    return mesh


def mesh_axis_sizes() -> dict:
    mesh = _ambient_mesh()
    if mesh is None:
        return {}
    return dict(zip(mesh.axis_names, mesh.shape.values() if hasattr(mesh.shape, "values")
                    else mesh.shape))


def _axis_size(sizes: dict, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= sizes.get(a, 1)
        return n
    return sizes.get(axis, 1)


def constrain(x, spec: Sequence[Axis]):
    """with_sharding_constraint(x, P(*spec)) with divisibility guards."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    sizes = dict(zip(mesh.axis_names,
                     mesh.shape.values() if hasattr(mesh.shape, "values") else mesh.shape))
    fixed = []
    for axis, dim in zip(spec, x.shape):
        if axis is None:
            fixed.append(None)
            continue
        names = axis if isinstance(axis, tuple) else (axis,)
        if not all(n in sizes for n in names):
            fixed.append(None)
            continue
        fixed.append(axis if dim % _axis_size(sizes, axis) == 0 else None)
    fixed += [None] * (x.ndim - len(fixed))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))


def batch_axes() -> Axis:
    sizes = mesh_axis_sizes()
    if "pod" in sizes:
        return ("pod", "data")
    if "data" in sizes:
        return "data"
    return None


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map``, with its varying-manual-axes check (``check_vma``)
    off unless the caller asks for it."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
