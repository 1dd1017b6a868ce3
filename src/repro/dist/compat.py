"""Small helpers over the jax mesh and export surface this repo drives.

Written for jax 0.9: ``jax.set_mesh``, ``jax.shard_map`` and
``jax.sharding.AxisType`` are used directly at their call sites.  What stays
here is what every caller would otherwise repeat: meshes with ``Auto`` axes
(``jax.make_mesh`` defaults to ``Explicit``, under which
``with_sharding_constraint`` asserts instead of constraining), the active
mesh or ``None``, axis sizes of concrete and abstract meshes alike, and the
portable executable blobs :mod:`repro.persist` stores.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "current_mesh",
    "export_deserialize",
    "export_serialize",
    "make_mesh",
    "mesh_axis_sizes",
]


def make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None):
    """``jax.make_mesh`` whose axes default to ``AxisType.Auto``."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(
        axis_shapes, axis_names, axis_types=axis_types, devices=devices
    )


def current_mesh():
    """The mesh set by ``jax.set_mesh`` for tracing, or ``None`` outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def mesh_axis_sizes(mesh) -> dict:
    """{axis name: size} for concrete and abstract meshes alike."""
    shape = getattr(mesh, "shape", None)
    if shape is not None:
        return dict(shape)
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def export_serialize(fn, args) -> bytes | None:
    """AOT-export ``jit(fn)`` for ``args``' shapes to a portable blob, or None.

    The blob is the :mod:`jax.export` serialization of the traced program —
    closure constants (Jacobi tables, teleport vectors) baked in — and
    deserializes on the same jax version without re-running the Python that
    built ``fn``.  Returns ``None`` (callers then keep their freshly traced
    executable for this process only) when:

    * the program spans **more than one device** (a shard_map export pins the
      device assignment and refuses to load into a different-width context, so
      persisting it could never hit);
    * export itself rejects the program (exotic primitives).
    """
    from jax import export as jax_export

    try:
        specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tuple(args)
        )
        exported = jax_export.export(jax.jit(fn))(*specs)
        if exported.nr_devices != 1:
            return None
        return exported.serialize()
    except Exception:
        return None


def export_deserialize(blob: bytes):
    """The jit-able callable of a serialized export, or ``None`` on any failure.

    A corrupt, truncated, or version-incompatible blob is a cache *miss* (the
    caller re-traces), never an error surfaced to the solve path.
    """
    from jax import export as jax_export

    try:
        return jax_export.deserialize(blob).call
    except Exception:
        return None
