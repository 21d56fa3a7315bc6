"""Pallas TPU kernel for the CCG master step (paper Alg. 2, MP1).

The unrolled robust solver runs this reduction once per CCG iteration for
the whole task batch: mask the (P, F) recourse slab by the generated
scenarios, take the max over poles (η), add the first-stage cost, mask
infeasible options to BIG, and argmin over F.  XLA executes that as four
separate HBM-bound elementwise/reduce ops over the (M, P, F) slab; here the
slab tile stays VMEM-resident and the whole chain runs in one pass.

Grid = (n_m, n_f) with F innermost: each (bm, P, bf) tile folds its local
min/argmin into the running per-task best, so the argmin streams over F
tiles without materializing the (M, F) objective.  Ties break to the lowest
flat index (strict-< across tiles, first-min within a tile), matching
``jnp.argmin``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ccg_master.ref import BIG

_INT_MAX = jnp.iinfo(jnp.int32).max


def _master_kernel(rec_ref, mask_ref, fsok_ref, c1_ref, y_ref, od_ref):
    fi = pl.program_id(1)
    bm, _, bf = rec_ref.shape

    mask = mask_ref[...]                                   # (bm, P)
    any_scen = mask.sum(axis=1, keepdims=True) > 0.0       # (bm, 1)
    active = jnp.where(mask[:, :, None] > 0.0, rec_ref[...], -BIG)
    eta = jnp.where(any_scen, active.max(axis=1), 0.0)     # (bm, bf)
    obj = jnp.where(fsok_ref[...] > 0.0, c1_ref[...] + eta, BIG)

    # first-min argmin for this tile, in global F coordinates
    idx = jax.lax.broadcasted_iota(jnp.int32, (bm, bf), 1) + fi * bf
    tile_min = obj.min(axis=1, keepdims=True)              # (bm, 1)
    tile_arg = jnp.where(obj == tile_min, idx, _INT_MAX).min(axis=1,
                                                             keepdims=True)

    @pl.when(fi == 0)
    def _():
        od_ref[...] = jnp.full((bm, 1), BIG, od_ref.dtype)
        y_ref[...] = jnp.zeros((bm, 1), y_ref.dtype)

    best = od_ref[...]
    better = tile_min < best                               # strict: first min wins
    od_ref[...] = jnp.where(better, tile_min, best)
    y_ref[...] = jnp.where(better, tile_arg, y_ref[...])


def ccg_master(rec_all, scen_mask, fs_ok, c1, *, block_m: int = 128,
               block_f: int = 128, interpret: bool = False):
    """rec_all: (M, P, F); scen_mask: (M, P); fs_ok: (M, F) float 0/1;
    c1: (1, F) -> (y_star (M, 1) int32, o_down (M, 1) float32).

    The per-task outputs are (M, 1) columns (every block 2-D or 3-D).  M
    must divide block_m and F divide block_f (the ops wrapper pads).
    """
    m, p, f = rec_all.shape
    bm = min(block_m, m)
    bf = min(block_f, f)
    assert m % bm == 0 and f % bf == 0
    grid = (m // bm, f // bf)

    col = lambda: pl.BlockSpec((bm, 1), lambda mi, fi: (mi, 0))
    return pl.pallas_call(
        _master_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, p, bf), lambda mi, fi: (mi, 0, fi)),
            pl.BlockSpec((bm, p), lambda mi, fi: (mi, 0)),
            pl.BlockSpec((bm, bf), lambda mi, fi: (mi, fi)),
            pl.BlockSpec((1, bf), lambda mi, fi: (0, fi)),
        ],
        out_specs=[col(), col()],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(rec_all, scen_mask, fs_ok, c1)
