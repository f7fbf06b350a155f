"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests and benches run on 1 real CPU device;
only launch/dryrun.py requests 512 placeholder devices).

Axes:
  data  — GP kernel-matrix ROW partitions / LM batch (FSDP) axis
  model — GP kernel-matrix COLUMN partitions / LM tensor axis
  pod   — multi-pod data-parallel replica axis (gradient all-reduce crosses
          the inter-pod links; everything bandwidth-hungry stays intra-pod)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(axes) -> tuple:
    # jax.make_mesh defaults to Explicit axes; this code base shards with
    # device_put + shard_map and lets XLA propagate the rest (Auto), so
    # arrays leaving a mesh step (trained params) mix with unsharded ones
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(axes))


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over the first data * model local devices (all of them
    when `data` is None) — so a 1x1 mesh on a four-chip host holds one."""
    devices = jax.devices()
    if data is None:
        data = len(devices) // model
    axes = ("data", "model")
    return jax.make_mesh((data, model), axes, _auto(axes),
                         devices=devices[:data * model])


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes(mesh) -> tuple[str, ...]:
    """All batch-parallel axes present in the mesh (pod folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
