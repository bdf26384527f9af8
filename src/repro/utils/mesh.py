"""Mesh construction with Auto axis types.

``jax.make_mesh`` defaults to Explicit axes; every mesh in this repo is
consumed by ``jax.shard_map`` bodies and GSPMD sharding constraints that
expect Auto axes, so all mesh construction routes through here.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh_auto(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(tuple(axis_names)),
    )


__all__ = ["make_mesh_auto"]
