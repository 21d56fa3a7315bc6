"""jit'd public wrapper: dispatches the compiled Pallas kernel on TPU and
the jnp ref elsewhere (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ccg_master.kernel import ccg_master as _pallas
from repro.kernels.ccg_master.ref import ccg_master_ref as _ref
from repro.kernels.dispatch import pallas_interpret


@partial(jax.jit, static_argnames=("block_m", "block_f", "force"))
def ccg_master(rec_all, scen_mask, fs_ok, c1, *, block_m: int = 128,
               block_f: int = 128, force: str = "auto"):
    """Masked CCG master step for a task batch -> (y_star, o_down).

    rec_all: (M, P, F); scen_mask: (M, P) 0/1; fs_ok: (M, F) bool; c1: (F,).
    ``force``: see :func:`repro.kernels.dispatch.pallas_interpret`.  Both
    M and F are padded up to the kernel blocks, so any shape works: padded
    options are infeasible (they never win the argmin) and padded tasks are
    sliced off.
    """
    interpret = pallas_interpret(force)
    if interpret is None:
        return _ref(rec_all, scen_mask, fs_ok, c1)
    m, p, f = rec_all.shape
    bm = min(block_m, m)
    bf = min(block_f, f)
    pad_m = (-m) % bm
    pad_f = (-f) % bf
    if pad_m or pad_f:
        rec_all = jnp.pad(rec_all, ((0, pad_m), (0, 0), (0, pad_f)))
        scen_mask = jnp.pad(scen_mask, ((0, pad_m), (0, 0)))
        fs_ok = jnp.pad(fs_ok, ((0, pad_m), (0, pad_f)))
        c1 = jnp.pad(c1, (0, pad_f))
    y, o_down = _pallas(
        rec_all.astype(jnp.float32),
        scen_mask.astype(jnp.float32),
        fs_ok.astype(jnp.float32),
        c1.astype(jnp.float32)[None, :],
        block_m=bm, block_f=bf, interpret=interpret,
    )
    return y[:m, 0], o_down[:m, 0]
