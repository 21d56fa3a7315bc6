"""Pallas TPU kernel for the fused per-task CCG encoding.

One pass per M-tile produces everything the unrolled robust solver needs
from a task batch: the accuracy surface is evaluated version-by-version
straight from the (F,) normalized option coordinates (VPU elementwise, no
(M, F, K) tensor), thresholded into the feasible-version bitmask, and the
(M, P, F) recourse slab is folded in place as a masked running min over the
pole-scaled second-stage costs.  The (K, P, F) scaled-cost slab — the
recourse lookup in its unexpanded form — stays VMEM-resident across the
whole M sweep (a few tens of KB vs the (M, P, F) HBM traffic XLA's
gather-based lowering makes per task).

The masked min-fold is value-identical to gathering the (P, F, 2^K) subset
lookup at the bitmask: entry ``[p, f, c]`` of that lookup *is*
``min_{k ∈ c} b2s[k, p, f]`` (BIG when c = ∅), and float min is exact, so
folding the same set elementwise reproduces the gather bit-for-bit.  Grid =
(n_m,): M is streamed in tiles, F (50 for the paper lattice) and the P ≤ 2^K
poles stay resident.  The running accuracy argmax hands off across versions
with strict-> / tie-to-lower-flat-index, matching ``jnp.argmax`` over the
(F·K) flat space (k minor).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cost_model import _accuracy_formula
from repro.kernels.ccg_master.ref import BIG

_INT_MAX = jnp.iinfo(jnp.int32).max


def _encode_kernel(z_ref, aq_ref, rn_ref, pn_ref, tf_ref, ok_ref, b2s_ref,
                   code_ref, rec_ref, best_ref, *, margin, num_versions):
    bm = z_ref.shape[0]
    f = rn_ref.shape[1]
    p = b2s_ref.shape[1]

    z = z_ref[...]                                       # (bm, 1) columns
    thr = aq_ref[...] + margin
    rn = rn_ref[...]                                     # (1, F) rows
    pn = pn_ref[...]
    tf = tf_ref[...]
    ok = ok_ref[...] > 0                                 # availability
    fidx = jax.lax.broadcasted_iota(jnp.int32, (bm, f), 1)

    code = jnp.zeros((bm, f), jnp.int32)
    rec = jnp.full((bm, p, f), BIG, jnp.float32)
    best_val = jnp.full((bm, 1), -BIG, jnp.float32)
    best = jnp.zeros((bm, 1), jnp.int32)
    for k in range(num_versions):
        f_k = _accuracy_formula(z, rn, pn, jnp.float32(k), tf)   # (bm, F)
        f_k = jnp.where(ok, f_k, -BIG)
        feas = f_k >= thr
        code = code + jnp.where(feas, jnp.int32(1 << k), 0)
        rec = jnp.where(feas[:, None, :],
                        jnp.minimum(rec, b2s_ref[k][None]), rec)
        # first-max argmax over F for this version, then strict hand-off
        row_max = f_k.max(axis=1, keepdims=True)
        row_arg = jnp.where(f_k == row_max, fidx, _INT_MAX).min(
            axis=1, keepdims=True)
        flat_k = row_arg * num_versions + k
        better = (row_max > best_val) | ((row_max == best_val) & (flat_k < best))
        best = jnp.where(better, flat_k, best)
        best_val = jnp.where(better, row_max, best_val)

    code_ref[...] = code
    rec_ref[...] = rec
    best_ref[...] = best


def ccg_encode(z, aq, rn_flat, pn_flat, tier_flat, y_ok, b2_scaled, *,
               margin: float, num_versions: int, block_m: int = 128,
               interpret: bool = False):
    """z/aq: (M, 1); rn/pn/tier_flat/y_ok: (1, F) — y_ok is the
    availability mask (all-ones when no outage); b2_scaled: (K, P, F)
    pole-scaled second-stage costs -> (code (M, F) int32, rec_all (M, P, F)
    float32, best (M, 1) int32).  Per-task vectors travel as (M, 1) columns
    and per-option vectors as (1, F) rows (every block 2-D).  M must divide
    block_m (the ops wrapper pads)."""
    m = z.shape[0]
    f = rn_flat.shape[1]
    k, p, _ = b2_scaled.shape
    bm = min(block_m, m)
    assert m % bm == 0 and k == num_versions
    grid = (m // bm,)

    col = lambda: pl.BlockSpec((bm, 1), lambda mi: (mi, 0))
    row = lambda: pl.BlockSpec((1, f), lambda mi: (0, 0))
    return pl.pallas_call(
        partial(_encode_kernel, margin=margin, num_versions=num_versions),
        grid=grid,
        in_specs=[
            col(), col(), row(), row(), row(), row(),
            pl.BlockSpec((k, p, f), lambda mi: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, f), lambda mi: (mi, 0)),
            pl.BlockSpec((bm, p, f), lambda mi: (mi, 0, 0)),
            col(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, f), jnp.int32),
            jax.ShapeDtypeStruct((m, p, f), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
        ],
        interpret=interpret,
    )(z, aq, rn_flat, pn_flat, tier_flat, y_ok, b2_scaled)
