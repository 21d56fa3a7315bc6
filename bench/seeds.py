"""Seeded streams: every input, weight and sample of a run is drawn from
``--seed`` through one of these, each under a stream name of its own, so
that two parts of a run never share draws and one seed always gives the
same run.  Seeds may exceed 32 bits."""
from __future__ import annotations

import zlib

import numpy as np


def _tag(stream: str) -> int:
    return zlib.crc32(stream.encode())


def np_rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of one seed."""
    return np.random.default_rng([int(seed), _tag(stream)])


def jax_key(seed: int, stream: str):
    """A JAX key for one named stream of one seed (all 64 bits used)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(_tag(stream) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
