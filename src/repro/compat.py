"""Mesh construction, in one place for the whole repo.

``make_mesh`` marks every axis ``Auto``: the train step shards its batch
with ``with_sharding_constraint``, which only accepts Auto axes, while
``jax.make_mesh`` without ``axis_types`` gives Explicit ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types, over ``devices`` (default:
    every device JAX sees)."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes),
        names,
        axis_types=(AxisType.Auto,) * len(names),
        devices=devices,
    )
