"""Mesh factories.

``make_production_mesh`` builds the assigned production meshes:
single-pod (16, 16) over ("data", "model") — 256 chips — and multi-pod
(2, 16, 16) over ("pod", "data", "model") — 512 chips. It is a FUNCTION so
importing this module never touches jax device state; the dry-run driver
sets XLA_FLAGS for 512 placeholder devices before any jax import.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    # jax.make_mesh defaults to Explicit axes; the sync's reshards
    # (packing.reshard_in / reshard_out / unpack_to_shardings) use
    # with_sharding_constraint, which only accepts Auto axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """``(data, model)`` mesh over the first ``data * model`` devices: the
    trainer's mesh on one chip (1, 1) or a four-chip host (4, 1), and the
    sharding tests' mesh on forced host devices."""
    return _auto_mesh((data, model), ("data", "model"))


def worker_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_workers(mesh) -> int:
    out = 1
    for a in worker_axes(mesh):
        out *= mesh.shape[a]
    return out
