"""jit'd public wrapper: dispatches the compiled Pallas kernel on TPU and
the jnp ref elsewhere (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import pallas_interpret
from repro.kernels.temporal_gate.kernel import gate_cell as _pallas
from repro.kernels.temporal_gate.ref import gate_cell_ref as _ref


@partial(jax.jit, static_argnames=("block_b", "force"))
def gate_cell(dx, h, vol, p, *, block_b: int = 256, force: str = "auto"):
    """Fused gating cell for a (B, d) stream batch -> (h_new, tau, g_mean).

    ``force``: see :func:`repro.kernels.dispatch.pallas_interpret`.  The
    batch is padded up to a multiple of the kernel block so any B works.
    """
    interpret = pallas_interpret(force)
    if interpret is None:
        return _ref(dx, h, vol, p)
    b = dx.shape[0]
    bb = min(block_b, b)
    pad = (-b) % bb
    rows = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    h_new, tau, g_mean = _pallas(rows(dx), rows(h), rows(vol[:, None]), p,
                                 block_b=bb, interpret=interpret)
    return h_new[:b], tau[:b, 0], g_mean[:b, 0]
