"""jit'd public wrapper: dispatches the compiled Pallas kernel on TPU and
the jnp ref elsewhere (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ccg_encode.kernel import ccg_encode as _pallas
from repro.kernels.ccg_encode.ref import ccg_encode_ref as _ref
from repro.kernels.dispatch import pallas_interpret


@partial(jax.jit, static_argnames=("margin", "num_versions", "block_m", "force"))
def ccg_encode(z, aq, rn_flat, pn_flat, tier_flat, b2_scaled, rec_table, *,
               margin: float, num_versions: int, block_m: int = 128,
               force: str = "auto", y_ok=None):
    """Fused per-task CCG encoding -> (code, rec_all, best).

    z/aq: (M,) task difficulty and accuracy requirement; rn/pn/tier_flat:
    (F,) normalized option coordinates; b2_scaled: (P, F, K) pole-scaled
    second-stage costs (the kernel's VMEM-resident recourse source);
    rec_table: (P, F, 2^K) subset-min lookup (the ref's gather source — the
    two encode the same recourse values, see kernel.py).  ``y_ok`` is an
    optional (F,) availability mask: options at ``y_ok <= 0`` become
    infeasible and lose the fallback argmax (scenario outages).  Returns the
    (M, F) int32 feasible-version bitmask, the (M, P, F) recourse slab, and
    the (M,) flat accuracy argmax used by the all-infeasible fallback.

    ``force``: see :func:`repro.kernels.dispatch.pallas_interpret`.  M is
    padded up to the kernel block, so any batch size works.
    """
    interpret = pallas_interpret(force)
    if interpret is None:
        return _ref(z, aq, rn_flat, pn_flat, tier_flat, rec_table,
                    margin, num_versions, y_ok=y_ok)
    m = z.shape[0]
    bm = min(block_m, m)
    pad_m = (-m) % bm
    col = lambda x: jnp.pad(x.astype(jnp.float32), (0, pad_m))[:, None]
    row = lambda x: jnp.asarray(x, jnp.float32)[None, :]
    ok = jnp.ones_like(rn_flat) if y_ok is None else y_ok
    code, rec_all, best = _pallas(
        col(z), col(aq),
        row(rn_flat), row(pn_flat), row(tier_flat), row(ok),
        jnp.moveaxis(b2_scaled, -1, 0).astype(jnp.float32),   # (K, P, F)
        margin=margin, num_versions=num_versions, block_m=bm,
        interpret=interpret,
    )
    return code[:m], rec_all[:m], best[:m, 0]
