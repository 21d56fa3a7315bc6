"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax

from repro.sharding.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Mesh over whatever devices exist (smoke tests / examples: 1 CPU)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))
