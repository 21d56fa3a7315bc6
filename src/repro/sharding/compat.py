"""Mesh construction, ``shard_map`` and batch-padding helpers.

Every mesh in the repo is built by :func:`make_mesh`, with Auto axes:
``jax.make_mesh`` defaults to Explicit axes, under which eager slicing of a
``shard_map`` output raises a sharding-type error.  Every shard_map user
(pipeline parallelism, the sharded CCG sweep, the sharded serve scan,
compressed collectives) imports :func:`shard_map` from here, and every
sharded entry point that rounds a task/stream batch up to the device count
uses :func:`pad_leading`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

shard_map = jax.shard_map


def make_mesh(shape, axes, *, devices=None):
    """A ``jax.sharding.Mesh`` of ``shape`` over ``axes`` with Auto axes.

    ``devices`` defaults to ``jax.devices()``; pass a subset (survivor
    meshes) or described devices (compile-only rehearsals)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def pad_leading(x, pad: int, value=0, axis: int = 0):
    """Pad the batch ``axis`` of ``x`` by ``pad`` rows of ``value``.

    The shared idiom behind M-to-any-device-count sharding: pad with inert
    dummies, shard, slice the real batch back out.  ``axis`` defaults to the
    leading axis; round-stacked (R, M, ...) streams pad ``axis=1`` directly
    instead of a moveaxis round-trip per field.
    """
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)
