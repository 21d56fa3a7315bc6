"""Pallas TPU kernel for the fused C6 repair tail.

One pass per M-tile computes a repair round's per-task quantities — current
bandwidth draw, both candidate-demotion accuracies, and the reclaimable
gain — with the route-indexed (bm, N·Z) bandwidth panel tile and the (N,) /
(Z,) coordinate vectors VMEM-resident.  The dynamic row gathers of the jnp
ref become one-hot max selects (exact: masked-out entries contribute -BIG),
and the accuracy formula is evaluated pointwise on the selected coordinates,
so the kernel is bit-identical to ``c6_tail_ref`` (tests/test_kernels.py).

The global demotion choice (descending-gain argsort + cumulative-gain
prefix) is a cross-task reduction and stays outside the kernel in
``enforce_bandwidth``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cost_model import _accuracy_formula
from repro.kernels.ccg_master.ref import BIG


def _tail_kernel(panel_ref, r_ref, p_ref, v_ref, route_ref, z_ref, thr_ref,
                 rn_ref, pn_ref, bw_ref, gain_ref, canp_ref, *, n_fps):
    bm, nz_flat = panel_ref.shape
    n = rn_ref.shape[1]
    z_n = pn_ref.shape[1]
    panel = panel_ref[...]
    r = r_ref[...]                                       # (bm, 1) columns
    p = p_ref[...]
    z = z_ref[...]
    thr = thr_ref[...]
    rn = rn_ref[...]                                     # (1, N)
    pn = pn_ref[...]                                     # (1, Z)
    flat_idx = jax.lax.broadcasted_iota(jnp.int32, (bm, nz_flat), 1)
    n_idx = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
    z_idx = jax.lax.broadcasted_iota(jnp.int32, (bm, z_n), 1)

    def take_bw(ri, pi):
        oh = flat_idx == ri * n_fps + pi
        return jnp.where(oh, panel, -BIG).max(axis=1, keepdims=True)

    def sel(idx_grid, vec, idx):
        return jnp.where(idx_grid == idx, vec, -BIG).max(axis=1, keepdims=True)

    bw = take_bw(r, p)
    p_dn = jnp.maximum(p - 1, 0)
    r_dn = jnp.maximum(r - 1, 0)
    vf = v_ref[...].astype(jnp.float32)
    tf = route_ref[...].astype(jnp.float32)
    f_pdn = _accuracy_formula(z, sel(n_idx, rn, r), sel(z_idx, pn, p_dn), vf, tf)
    f_rdn = _accuracy_formula(z, sel(n_idx, rn, r_dn), sel(z_idx, pn, p), vf, tf)
    can_p = (p > 0) & (f_pdn >= thr)
    can_r = (r > 0) & (f_rdn >= thr)
    gain_p = bw - take_bw(r, p_dn)
    gain_r = bw - take_bw(r_dn, p)
    gain = jnp.where(can_p, gain_p, jnp.where(can_r, gain_r, -BIG))

    bw_ref[...] = bw
    gain_ref[...] = gain
    canp_ref[...] = can_p.astype(jnp.int32)


def c6_tail(bw_panel, r, p, v, route, z, acc_thr, rn, pn, *, n_fps: int,
            block_m: int = 256, interpret: bool = False):
    """bw_panel: (M, N·Z); r/p/v/route: (M, 1) int32; z/acc_thr: (M, 1);
    rn: (1, N) / pn: (1, Z) -> (bw, gain, can_p int32), each (M, 1).

    Per-task vectors travel as (M, 1) columns: tasks on sublanes, in the
    same orientation as the panel rows (Mosaic refuses 1-D lane blocks that
    are a strict part of their array).  M must divide block_m (the ops
    wrapper pads)."""
    m, nz_flat = bw_panel.shape
    n = rn.shape[1]
    z_n = pn.shape[1]
    bm = min(block_m, m)
    assert m % bm == 0 and nz_flat == n * n_fps
    grid = (m // bm,)

    col = lambda: pl.BlockSpec((bm, 1), lambda mi: (mi, 0))
    return pl.pallas_call(
        partial(_tail_kernel, n_fps=n_fps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, nz_flat), lambda mi: (mi, 0)),
            col(), col(), col(), col(), col(), col(),
            pl.BlockSpec((1, n), lambda mi: (0, 0)),
            pl.BlockSpec((1, z_n), lambda mi: (0, 0)),
        ],
        out_specs=[col(), col(), col()],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
        ],
        interpret=interpret,
    )(bw_panel, r, p, v, route, z, acc_thr, rn, pn)
