"""The mesh / shard_map / compiled-program API surface the distributed modules
share: ``shard_map`` with the ``check_vma`` flag, Auto-axis ``make_mesh``, and
the compiled program's cost and memory analyses as plain dicts.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (``{}`` when the backend has
    none)."""
    return dict(compiled.cost_analysis() or {})


def memory_stats(compiled) -> dict:
    """``compiled.memory_analysis()`` as the dryrun report dict."""
    mem = compiled.memory_analysis()
    return {
        "bytes_per_device": mem.temp_size_in_bytes,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "peak_bytes": mem.peak_memory_in_bytes,
    }


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (plain ``jax.make_mesh``
    makes them ``Explicit``)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_shapes),
                         devices=devices)


__all__ = ["shard_map", "make_mesh", "cost_analysis", "memory_stats"]
