"""jit'd public wrapper: dispatches the compiled Pallas kernel on TPU and
the jnp ref elsewhere (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.mamba_scan.kernel import selective_scan as _pallas
from repro.kernels.mamba_scan.ref import selective_scan_ref as _ref
from repro.kernels.dispatch import pallas_interpret


@partial(jax.jit, static_argnames=("block_t", "block_d", "force"))
def selective_scan(x, dt, B, C, A, D, h0=None, *, block_t: int = 128,
                   block_d: int = 512, force: str = "auto"):
    interpret = pallas_interpret(force)
    if interpret is not None:
        return _pallas(x, dt, B, C, A, D, h0, block_t=block_t, block_d=block_d,
                       interpret=interpret)
    return _ref(x, dt, B, C, A, D, h0)
