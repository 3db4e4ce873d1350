"""Mesh construction and shard_map capability notes for the installed jax.

``make_mesh`` pins every axis to ``AxisType.Auto`` (the sharding-in-types
``Explicit`` default would change how the solver's shard_maps and jits
propagate shardings). ``PARTIAL_MANUAL_SHARD_MAP`` records that
shard_map with *partial* manual axes (manual: some, auto: the rest) —
the compressed pod-axis gradient fusion of ``launch/steps.py`` — is not
usable: the XLA SPMD partitioner aborts the process with a CHECK in
``spmd_partitioner_util.cc`` when it lowers that pattern, so it cannot
even be probed at run time. Fully-manual shard_map — every solver path,
``compressed_psum``, ``AmpEngine.solve_sharded`` — is unaffected.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "PARTIAL_MANUAL_SHARD_MAP"]

PARTIAL_MANUAL_SHARD_MAP = False


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_shapes))
