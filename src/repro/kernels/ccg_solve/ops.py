"""jit'd public wrapper: dispatches the compiled Pallas kernel on TPU and
the jnp ref elsewhere (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ccg_solve.kernel import ccg_solve as _pallas
from repro.kernels.ccg_solve.ref import ccg_solve_ref as _ref
from repro.kernels.dispatch import pallas_interpret


@partial(jax.jit, static_argnames=("margin", "num_versions", "max_iters",
                                   "theta", "block_m", "force"))
def ccg_solve(z, aq, rn_flat, pn_flat, tier_flat, b2_flat, u_all, c1_flat,
              warm_y, *, margin: float, num_versions: int, max_iters: int = 8,
              theta: float = 1e-4, block_m: int = 128, force: str = "auto",
              y_ok=None):
    """Fully fused CCG solve -> (y_f, v_star, o_up, o_down, iters, infeasible).

    z/aq: (M,) task difficulty and accuracy requirement; rn/pn/tier_flat:
    (F,) normalized option coordinates; b2_flat: (F, K) second-stage costs;
    u_all: (P, K) pole deviations; c1_flat: (F,) first-stage costs; warm_y:
    (M,) int32 flat warm starts (-1 = cold); y_ok: optional (F,) availability
    mask — options at ``y_ok <= 0`` become infeasible and lose the fallback
    argmax (scenario outages).  Runs encode -> master argmin ->
    SP pole selection -> η update across all min(max_iters, P+1) CCG steps in
    one pass — no per-step dispatch, no (M, P, F) recourse slab.

    ``force``: see :func:`repro.kernels.dispatch.pallas_interpret`.  M is
    padded up to the kernel block; padded lanes are cold, all-infeasible-safe
    dummies sliced off before returning.
    """
    interpret = pallas_interpret(force)
    if interpret is None:
        return _ref(z, aq, rn_flat, pn_flat, tier_flat, b2_flat, u_all,
                    c1_flat, warm_y, margin, num_versions, max_iters, theta,
                    y_ok=y_ok)
    m = z.shape[0]
    bm = min(block_m, m)
    pad_m = (-m) % bm
    col = lambda x, dt, fill=0: jnp.pad(x.astype(dt), (0, pad_m),
                                        constant_values=fill)[:, None]
    row = lambda x: jnp.asarray(x, jnp.float32)[None, :]
    ok = jnp.ones_like(rn_flat) if y_ok is None else y_ok
    y_f, v_star, o_up, o_down, iters, infeas = _pallas(
        col(z, jnp.float32), col(aq, jnp.float32),
        col(warm_y, jnp.int32, fill=-1),
        row(rn_flat), row(pn_flat), row(tier_flat), row(ok),
        jnp.moveaxis(b2_flat, -1, 0).astype(jnp.float32),    # (K, F)
        jnp.moveaxis(u_all, -1, 0).astype(jnp.float32),      # (K, P)
        row(c1_flat),
        margin=margin, num_versions=num_versions, max_iters=max_iters,
        theta=theta, block_m=bm, interpret=interpret,
    )
    return (y_f[:m, 0], v_star[:m, 0], o_up[:m, 0], o_down[:m, 0],
            iters[:m, 0], infeas[:m, 0] > 0)
