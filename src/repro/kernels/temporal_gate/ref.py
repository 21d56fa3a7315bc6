"""Pure-jnp oracle for the fused temporal-gating cell (paper Eq. 5-6)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gate_cell_ref(dx, h, vol, p):
    """One fused gating step for a batch of streams.

    dx: (B, d); h: (B, m); vol: (B,) volatility Var(Δx_{t-T:t}).
    p: dict with w_g,u_g,b_g,alpha,w_r,u_r,b_r,w_h,u_h,b_h,w_o,b_o.
    Returns (h_new (B, m), tau (B,), g_mean (B,)).

    The three dx-projections and the two h-projections are packed into one
    (d, 3m) and one (m, 2m) matmul each — four GEMMs per step instead of
    six.  Each output column's reduction is unchanged by the packing, so
    the gates are numerically identical to the historical separate-matmul
    form (tests lock the kernel/ref pair bit for bit).
    """
    m = h.shape[1]
    # f32 matmuls on every backend (the TPU default is one bf16 pass): τ
    # feeds threshold decisions, which must match the kernel's
    mm = lambda a, b: jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    w_x = jnp.concatenate([p["w_g"], p["w_r"], p["w_h"]], axis=1)   # (d, 3m)
    u_gr = jnp.concatenate([p["u_g"], p["u_r"]], axis=1)            # (m, 2m)
    xw = mm(dx, w_x)                                                # (B, 3m)
    hu = mm(h, u_gr)                                                # (B, 2m)
    g = jax.nn.sigmoid(xw[:, :m] + hu[:, :m] + p["b_g"]
                       + (p["alpha"] * vol)[:, None])
    r = jax.nn.sigmoid(xw[:, m:2 * m] + hu[:, m:] + p["b_r"])
    cand = jnp.tanh(xw[:, 2 * m:] + mm(r * h, p["u_h"]) + p["b_h"])
    h_new = (1.0 - g) * h + g * cand
    tau = jax.nn.sigmoid(mm(h_new, p["w_o"]) + p["b_o"])[:, 0]
    return h_new, tau, g.mean(axis=-1)
