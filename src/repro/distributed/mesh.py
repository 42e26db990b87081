"""Mesh utilities for the distributed clustering runtime.

The *production* mesh lives in ``repro.launch.mesh`` (16x16 single-pod /
2x16x16 multi-pod). The helpers here build correctness-test meshes from
whatever devices exist (e.g. 8 forced host devices) and answer axis-shape
questions without touching global device state.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_test_mesh(axes: dict[str, int] | None = None) -> Mesh:
    """Mesh over the available devices; default splits them into
    (data, model) with the largest power-of-two model axis <= sqrt(n)."""
    devices = jax.devices()
    n = len(devices)
    if axes is None:
        model = 1
        while model * 2 <= int(math.isqrt(n)) and n % (model * 2) == 0:
            model *= 2
        axes = {"data": n // model, "model": model}
    shape = tuple(axes.values())
    if math.prod(shape) != n:
        raise ValueError(f"mesh {axes} needs {math.prod(shape)} devices, have {n}")
    from .compat import make_mesh
    return make_mesh(shape, tuple(axes.keys()))


def auto_axes(mesh: Mesh) -> Mesh:
    """The caller's mesh with every axis ``Auto``. The mesh runners index
    row-sharded batches outside shard_map (``jnp.take``, ``x[:n]``), which
    an ``Explicit`` axis — what plain ``jax.make_mesh`` builds — refuses;
    on ``Auto`` axes the compiler inserts the gathers itself."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def axis_size(mesh: Mesh, names: tuple[str, ...] | str) -> int:
    if isinstance(names, str):
        names = (names,)
    out = 1
    for n in names:
        out *= mesh.shape[n]
    return out


def row_axes_of(mesh: Mesh) -> tuple[str, ...]:
    """Row (data-parallel) axes: every mesh axis except 'model'."""
    return tuple(n for n in mesh.axis_names if n != "model")


def ghost_row_ids(n: int, multiple: int) -> np.ndarray:
    """Source row ids for the ghost rows that pad an n-row batch up to a
    ``multiple`` of the mesh row count: head rows repeated modulo n, so a
    tail batch SMALLER than the mesh (a stream's last yield) pads correctly
    instead of indexing past the batch. Shared by the dense, CSR and exact
    staging paths — the replication convention must not drift apart."""
    if n < 1:
        raise ValueError("cannot stage an empty batch onto the mesh")
    return np.arange((-n) % multiple) % n
