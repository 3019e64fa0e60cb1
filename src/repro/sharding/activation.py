"""Activation sharding constraints that degrade gracefully.

``constrain(x, ("pod", "data"), None, "model")`` applies a
``with_sharding_constraint`` using only the mesh axes that actually exist in
the active mesh (so the same model code runs on a 1-CPU test mesh, a 256-chip
pod, or the 512-chip 2-pod mesh) and only when the named axis size divides
the corresponding dim.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec

BATCH_AXES = ("pod", "data")   # logical batch → physical axes (filtered)
SEQ_AXES = ("data",)           # sequence parallelism for long-context decode

_local = threading.local()


def batch_axes() -> tuple:
    """Physical axes the logical batch maps to (overridable per run —
    e.g. pure-FSDP spreads batch over (pod, data, model))."""
    return getattr(_local, "batch_axes", BATCH_AXES)


@contextlib.contextmanager
def use_batch_axes(axes: tuple):
    prev = getattr(_local, "batch_axes", BATCH_AXES)
    _local.batch_axes = tuple(axes)
    try:
        yield
    finally:
        _local.batch_axes = prev


def _active_mesh():
    try:
        from jax._src.mesh import thread_resources
        mesh = thread_resources.env.physical_mesh
    except Exception:  # pragma: no cover - API drift guard
        return None
    if mesh.empty:
        return None
    return mesh


def placement_mesh():
    """The mesh the program's shardings come from: the ``with mesh:``
    mesh, else the one ``jax.set_mesh`` or ``use_abstract_mesh`` entered
    (``with mesh:`` leaves the abstract mesh empty); None outside both."""
    mesh = _active_mesh()
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return None
    return mesh


def constrain(x: jax.Array, *axes) -> jax.Array:
    mesh = _active_mesh()
    if mesh is None:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = []
    for dim, a in zip(x.shape, axes):
        if a is None:
            entries.append(None)
            continue
        cand = a if isinstance(a, tuple) else (a,)
        cand = tuple(c for c in cand if c in sizes)
        total = 1
        for c in cand:
            total *= sizes[c]
        if cand and total > 1 and dim % total == 0:
            entries.append(cand if len(cand) > 1 else cand[0])
        else:
            entries.append(None)
    spec = PartitionSpec(*entries)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
