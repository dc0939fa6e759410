"""Mesh construction with Auto axis types.

``jax.make_mesh`` gives Explicit axes by default.  The SpMV programs place
arrays with ``NamedSharding`` and run manual collectives inside
``jax.shard_map``, which is the Auto-axis model, so every mesh in the repo
is built here.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh"]


def make_mesh(
    axis_shapes: Sequence[int], axis_names: Sequence[str], devices=None
) -> Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names, axis_types=(AxisType.Auto,) * len(names),
        devices=devices,
    )
