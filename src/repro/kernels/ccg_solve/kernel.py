"""Pallas TPU kernel for the fully fused CCG solve (paper Alg. 2).

One pass per M-tile runs the *entire* column-and-constraint alternation:
encode (accuracy formula -> feasible-version bitmask), then
min(max_iters, P+1) unrolled master/adversary steps — feasibility-masked
argmin over the F flat options, exact SP pole selection, running (bm, F)
η-max — and the final-recourse epilogue, all without leaving VMEM.  The
(F, K) cost table, (P, K) pole deviations, and (F,) coordinate/cost vectors
are broadcast blocks resident across the whole M sweep; the per-lane state
(η slab, bounds, incumbent, done flags) lives in registers/VMEM for all
steps, so the solve makes zero HBM round-trips between CCG iterations.

Bit-parity contract with ``ccg_solve_ref`` (and hence ``solve_ccg`` /
``solve_ccg_while``): every argmin/argmax is min/max + masked-iota-min
(first index achieving the extremum — identical tie-breaking); row gathers
are one-hot max/sum selects (exact: the masked-out lanes contribute -BIG to
a max or 0 to an integer sum); recourse values are K-fold masked mins over
the same products the (P, F, 2^K) lookup was built from, and float min is
exact.  Done lanes are frozen by live-gating every state write, so the full
unroll (no early exit inside a kernel) is bit-identical to the ref's
early-exiting while_loop.  Covered by tests/test_kernels.py in interpret
mode.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cost_model import _accuracy_formula
from repro.kernels.ccg_master.ref import BIG

_INT_MAX = jnp.iinfo(jnp.int32).max


def _solve_kernel(z_ref, aq_ref, wy_ref, rn_ref, pn_ref, tf_ref, ok_ref,
                  b2k_ref, ut_ref, c1_ref, y_ref, v_ref, oup_ref, odn_ref,
                  it_ref, inf_ref, *, margin, num_versions, n_steps, theta):
    bm = z_ref.shape[0]
    f = rn_ref.shape[1]
    k_n = num_versions
    p_n = ut_ref.shape[1]

    z = z_ref[...]                                        # (bm, 1) columns
    thr = aq_ref[...] + margin
    rn = rn_ref[...]                                      # (1, F) rows
    pn = pn_ref[...]
    tf = tf_ref[...]
    ok = ok_ref[...] > 0                                  # availability
    c1 = c1_ref[...]
    b2k = [b2k_ref[k:k + 1, :] for k in range(k_n)]       # (1, F) per version
    u_k = [ut_ref[k:k + 1, :] for k in range(k_n)]        # (1, P) per version
    opu = [1.0 + u for u in u_k]
    bit_of = [jnp.int32(1 << k) for k in range(k_n)]
    fidx = jax.lax.broadcasted_iota(jnp.int32, (bm, f), 1)
    pidx = jax.lax.broadcasted_iota(jnp.int32, (bm, p_n), 1)

    def sel(iota, row, idx):
        """row[idx] for a (1, n) row and (bm, 1) idx — one-hot max select."""
        return jnp.where(iota == idx, row, -BIG).max(axis=1, keepdims=True)

    def first(iota, vals, target):
        """First index at which vals (bm, n) equals target (bm, 1)."""
        return jnp.where(vals == target, iota, _INT_MAX).min(axis=1,
                                                             keepdims=True)

    # ---- encode: feasibility bitmask + flat accuracy argmax ----
    code = jnp.zeros((bm, f), jnp.int32)
    bv = jnp.zeros((bm, f), jnp.float32)
    bk = jnp.zeros((bm, f), jnp.int32)
    for k in range(k_n):
        f_k = _accuracy_formula(z, rn, pn, jnp.float32(k), tf)    # (bm, F)
        f_k = jnp.where(ok, f_k, -BIG)
        code = code + jnp.where(f_k >= thr, bit_of[k], 0)
        if k == 0:
            bv = f_k
        else:
            up = f_k > bv
            bv = jnp.where(up, f_k, bv)
            bk = jnp.where(up, k, bk)
    by = first(fidx, bv, bv.max(axis=1, keepdims=True))
    bk_y = jnp.where(fidx == by, bk, 0).max(axis=1, keepdims=True)
    fs_ok = code > 0

    def sp_at(y):
        """(bm, P) recourse of option y at every pole — K-fold select."""
        oh = fidx == y
        cy = jnp.where(oh, code, 0).max(axis=1, keepdims=True)    # (bm, 1)
        sp = jnp.full((bm, p_n), BIG, jnp.float32)
        for k in range(k_n):
            b2y_k = jnp.where(oh, b2k[k], -BIG).max(axis=1, keepdims=True)
            term = b2y_k * opu[k]                          # (bm, P)
            sp = jnp.where((cy & bit_of[k]) != 0, jnp.minimum(sp, term), sp)
        return sp, cy

    def rec_at(pole):
        """(bm, F) recourse row of each lane's pole — K-fold select."""
        rec = jnp.full((bm, f), BIG, jnp.float32)
        for k in range(k_n):
            term = b2k[k] * sel(pidx, opu[k], pole)        # (bm, F)
            rec = jnp.where((code & bit_of[k]) != 0, jnp.minimum(rec, term),
                            rec)
        return rec

    # ---- warm start seeding ----
    wy = wy_ref[...]
    wyc = jnp.maximum(wy, 0)
    fs_wy = jnp.where(fidx == wyc, code, 0).max(axis=1, keepdims=True) > 0
    use_warm = (wy >= 0) & fs_wy
    rec_wy, _ = sp_at(wyc)
    q_w = rec_wy.max(axis=1, keepdims=True)
    warm_pole = first(pidx, rec_wy, q_w)
    o_up = jnp.where(use_warm, sel(fidx, c1, wyc) + q_w, BIG)
    eta_run = jnp.where(use_warm, rec_at(warm_pole), 0.0)

    o_down = jnp.full((bm, 1), -BIG, jnp.float32)
    y_best = wyc
    iters = jnp.zeros((bm, 1), jnp.int32)
    done = jnp.zeros((bm, 1), jnp.int32)     # int flag: Mosaic has no i1 state

    # ---- unrolled CCG alternation (live-gated, done lanes frozen) ----
    for _ in range(n_steps):
        live = done == 0
        obj = jnp.where(fs_ok, c1 + eta_run, BIG)
        od_new = obj.min(axis=1, keepdims=True)
        y_star = first(fidx, obj, od_new)
        sp_vals, _ = sp_at(y_star)
        q = sp_vals.max(axis=1, keepdims=True)
        worst_pole = first(pidx, sp_vals, q)
        cand = sel(fidx, c1, y_star) + q
        up_new = jnp.minimum(o_up, cand)
        y_best = jnp.where(live & (cand < o_up), y_star, y_best)
        o_down = jnp.where(live, od_new, o_down)
        o_up = jnp.where(live, up_new, o_up)
        eta_run = jnp.maximum(eta_run, rec_at(worst_pole))
        iters = iters + live.astype(jnp.int32)
        done = jnp.where(live & ((up_new - od_new) <= theta), 1, done)

    # ---- epilogue: final worst pole, v*, all-infeasible fallback ----
    sp_vals, code_y = sp_at(y_best)
    worst = first(pidx, sp_vals, sp_vals.max(axis=1, keepdims=True))
    oh_y = fidx == y_best
    vals = []
    for k in range(k_n):
        b2y_k = jnp.where(oh_y, b2k[k], -BIG).max(axis=1, keepdims=True)
        u_w = sel(pidx, u_k[k], worst)
        vals.append(jnp.where((code_y & bit_of[k]) != 0,
                              b2y_k * (1.0 + u_w), BIG))
    vmin = vals[0]
    for val in vals[1:]:
        vmin = jnp.minimum(vmin, val)
    v_star = jnp.full((bm, 1), _INT_MAX, jnp.int32)
    for k in reversed(range(k_n)):                        # first k at the min
        v_star = jnp.where(vals[k] == vmin, k, v_star)
    none_ok = code.max(axis=1, keepdims=True) == 0
    # the flat accuracy argmax is by * K + bk_y (k minor)
    y_ref[...] = jnp.where(none_ok, by, y_best)
    v_ref[...] = jnp.where(none_ok, bk_y, v_star)
    oup_ref[...] = o_up
    odn_ref[...] = o_down
    it_ref[...] = iters
    inf_ref[...] = none_ok.astype(jnp.int32)


def ccg_solve(z, aq, warm_y, rn_flat, pn_flat, tier_flat, y_ok, b2k, u_t,
              c1_flat, *, margin: float, num_versions: int, max_iters: int = 8,
              theta: float = 1e-4, block_m: int = 128,
              interpret: bool = False):
    """z/aq: (M, 1); warm_y: (M, 1) int32; rn/pn/tier_flat, c1_flat, y_ok:
    (1, F) — y_ok is the availability mask (all-ones when no outage);
    b2k: (K, F) transposed second-stage costs; u_t: (K, P) transposed pole
    deviations -> (y_f, v_star, o_up, o_down, iters, infeasible(int32)),
    each (M, 1).

    Per-task vectors travel as (M, 1) columns (tasks on sublanes, like the
    rows of every (bm, F) tile) and per-option vectors as (1, F) rows, so
    every block is 2-D and no value is reshaped inside the kernel.  M must
    divide block_m (the ops wrapper pads)."""
    m = z.shape[0]
    f = rn_flat.shape[1]
    k, p = num_versions, u_t.shape[1]
    bm = min(block_m, m)
    assert m % bm == 0 and b2k.shape == (k, f) and u_t.shape[0] == k
    n_steps = min(max_iters, p + 1)
    grid = (m // bm,)

    col = lambda: pl.BlockSpec((bm, 1), lambda mi: (mi, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda mi: (0, 0))
    return pl.pallas_call(
        partial(_solve_kernel, margin=margin, num_versions=num_versions,
                n_steps=n_steps, theta=theta),
        grid=grid,
        in_specs=[
            col(), col(), col(),
            whole((1, f)), whole((1, f)), whole((1, f)), whole((1, f)),
            whole((k, f)), whole((k, p)), whole((1, f)),
        ],
        out_specs=[col() for _ in range(6)],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
        ],
        interpret=interpret,
    )(z, aq, warm_y, rn_flat, pn_flat, tier_flat, y_ok, b2k, u_t, c1_flat)
