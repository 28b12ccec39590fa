"""Mesh construction for the production topologies.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.

Every mesh is built with ``AxisType.Auto`` axes: the model code places
activations with ``with_sharding_constraint`` (models/sharding.py), which only
accepts Auto axes, while ``jax.make_mesh`` defaults to Explicit ones.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "data_axes", "worker_count", "worker_index"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256 chips / pod) single-pod mesh, or 2x16x16 = 512-chip two-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary test mesh, e.g. ((2,2,2), ('pod','data','model'))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes that shard the batch (everything except 'model').  'node' is the
    optional intra-/inter-node boundary axis of the hierarchical topology
    (DESIGN.md §Topology): workers are ('node', 'data'), node-major."""
    return tuple(a for a in ("pod", "node", "data") if a in mesh.axis_names)


def worker_axes_in(mesh, requested: Sequence[str]) -> Tuple[str, ...]:
    """The DIANA worker axes actually present in this mesh."""
    return tuple(a for a in requested if a in mesh.axis_names)


def worker_count(mesh, worker_axes: Sequence[str]) -> int:
    n = 1
    for a in worker_axes_in(mesh, worker_axes):
        n *= mesh.shape[a]
    return max(n, 1)


def resolve_train_mesh(mesh, worker_axes: Sequence[str]):
    """Mesh actually used by the training step.

    XLA's SPMD partitioner RET_CHECKs (spmd_partitioner.cc:2584) on several
    ops whenever a shard_map has MORE THAN ONE manual axis.  When the DIANA
    workers span multiple mesh axes (paper-faithful mode on the multi-pod
    mesh), we therefore flatten the worker axes into a single 'data' axis,
    pod-major — the device order (and thus which chips communicate over the
    slow inter-pod links) is unchanged; only the name partitioning is.
    Hierarchical mode (workers = pods) keeps the full 3-axis mesh: one manual
    axis, and the inner 'data' axis stays auto for FSDP.

    Returns (step_mesh, worker_axes_in_step_mesh).
    """
    waxes = worker_axes_in(mesh, worker_axes)
    if len(waxes) <= 1:
        return mesh, waxes
    assert tuple(mesh.axis_names[: len(waxes)]) == tuple(waxes), (
        "worker axes must be the leading mesh axes to flatten pod-major"
    )
    other = tuple(a for a in mesh.axis_names if a not in waxes)
    n_w = 1
    for a in waxes:
        n_w *= mesh.shape[a]
    new_shape = (n_w,) + tuple(mesh.shape[a] for a in other)
    devices = mesh.devices.reshape(new_shape)
    names = ("data",) + other
    flat = jax.sharding.Mesh(devices, names,
                             axis_types=(AxisType.Auto,) * len(names))
    return flat, ("data",)


def worker_index(worker_axes: Sequence[str]):
    """Linearised worker index inside a shard_map body (row-major)."""
    import jax.numpy as jnp

    idx = jnp.zeros((), jnp.int32)
    for a in worker_axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx
