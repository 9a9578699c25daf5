"""Mesh construction with Auto axis types.

``jax.make_mesh`` defaults to Explicit axis types; every mesh in repo code
and tests shards through ``shard_map`` and sharding constraints, which want
Auto axes, so mesh construction goes through :func:`make_mesh`.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
