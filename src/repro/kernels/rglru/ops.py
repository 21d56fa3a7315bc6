"""jit'd public wrapper: dispatches the compiled Pallas kernel on TPU and
the jnp ref elsewhere (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rglru.kernel import rglru_scan as _pallas
from repro.kernels.rglru.ref import rglru_scan_ref as _ref
from repro.kernels.dispatch import pallas_interpret


@partial(jax.jit, static_argnames=("block_t", "block_w", "force"))
def rglru_scan(x, rgate, igate, log_a_base, h0=None, *, block_t: int = 128,
               block_w: int = 512, force: str = "auto"):
    interpret = pallas_interpret(force)
    if interpret is not None:
        return _pallas(x, rgate, igate, log_a_base, h0, block_t=block_t,
                       block_w=block_w, interpret=interpret)
    return _ref(x, rgate, igate, log_a_base, h0)
