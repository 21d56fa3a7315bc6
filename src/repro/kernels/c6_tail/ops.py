"""jit'd public wrapper: dispatches the compiled Pallas kernel on TPU and
the jnp ref elsewhere (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.c6_tail.kernel import c6_tail as _pallas
from repro.kernels.c6_tail.ref import c6_tail_ref as _ref
from repro.kernels.dispatch import pallas_interpret


@partial(jax.jit, static_argnames=("n_fps", "block_m", "force"))
def c6_tail(bw_panel, r, p, v, route, z, acc_thr, rn, pn, *, n_fps: int,
            block_m: int = 256, force: str = "auto"):
    """Fused C6 repair tail -> (bw, gain, can_p) for one demotion round.

    bw_panel: (M, N·Z) route-indexed bandwidth panel; r/p/v/route: (M,)
    decision indices; z: (M,) difficulty; acc_thr: (M,) accuracy floor
    (A^q + margin); rn/pn: (N,)/(Z,) normalized coordinates.

    ``force``: see :func:`repro.kernels.dispatch.pallas_interpret`.  M is
    padded up to the kernel block; padded lanes read panel row 0 with r=p=0
    (no demotion possible, gain -BIG) and are sliced off.
    """
    interpret = pallas_interpret(force)
    if interpret is None:
        return _ref(bw_panel, r, p, v, route, z, acc_thr, rn, pn, n_fps)
    m = bw_panel.shape[0]
    bm = min(block_m, m)
    pad_m = (-m) % bm
    col = lambda x, dt: jnp.pad(x.astype(dt), (0, pad_m))[:, None]
    bw, gain, can_p = _pallas(
        jnp.pad(bw_panel.astype(jnp.float32), ((0, pad_m), (0, 0))),
        col(r, jnp.int32), col(p, jnp.int32), col(v, jnp.int32),
        col(route, jnp.int32), col(z, jnp.float32), col(acc_thr, jnp.float32),
        rn.astype(jnp.float32)[None, :], pn.astype(jnp.float32)[None, :],
        n_fps=n_fps, block_m=bm, interpret=interpret,
    )
    return bw[:m, 0], gain[:m, 0], can_p[:m, 0] > 0
