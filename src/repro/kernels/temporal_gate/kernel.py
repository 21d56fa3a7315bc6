"""Pallas TPU fused temporal-gating cell (paper Eq. 5-6).

At fleet scale the router evaluates the gate for thousands of concurrent
streams per scheduling tick; the cell is a handful of small matmuls +
elementwise chains that XLA would execute as separate HBM round-trips.
This kernel fuses the whole step for a (BB, d) stream tile: the weight
matrices stay resident in VMEM, the tile makes a single pass, and the
batched streams ride the MXU rows.  Mirroring the ref, the three
dx-projections ride one packed (d, 3m) GEMM and the two h-projections one
(m, 2m) GEMM (column-sliced after), so the MXU sees four matmuls per tile
instead of six.

Grid = (n_b,); weights are broadcast blocks (same block for every program).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mm(a, b):
    # full f32 like the ref: τ feeds threshold decisions, and the MXU's
    # default single bf16 pass would move it by ~1e-3
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _gate_kernel(dx_ref, h_ref, vol_ref, wx_ref, ugr_ref, bg_ref, alpha_ref,
                 br_ref, uh_ref, bh_ref, wo_ref, bo_ref,
                 hout_ref, tau_ref, gmean_ref, *, m):
    dx = dx_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    vol = vol_ref[...].astype(jnp.float32)           # (BB, 1) column

    xw = _mm(dx, wx_ref[...])                        # (BB, 3m) packed g|r|h
    hu = _mm(h, ugr_ref[...])                        # (BB, 2m) packed g|r
    g = jax.nn.sigmoid(xw[:, :m] + hu[:, :m] + bg_ref[...]
                       + alpha_ref[...] * vol)
    r = jax.nn.sigmoid(xw[:, m:2 * m] + hu[:, m:] + br_ref[...])
    cand = jnp.tanh(xw[:, 2 * m:] + _mm(r * h, uh_ref[...]) + bh_ref[...])
    h_new = (1.0 - g) * h + g * cand
    tau = jax.nn.sigmoid(_mm(h_new, wo_ref[...]) + bo_ref[...])   # (BB, 1)
    hout_ref[...] = h_new.astype(hout_ref.dtype)
    tau_ref[...] = tau.astype(tau_ref.dtype)
    gmean_ref[...] = g.mean(axis=-1, keepdims=True).astype(gmean_ref.dtype)


def gate_cell(dx, h, vol, p, *, block_b: int = 256, interpret: bool = False):
    """dx: (B, d); h: (B, m); vol: (B, 1) -> (h_new (B, m), tau (B, 1),
    g_mean (B, 1)).

    Per-stream scalars travel as (B, 1) columns and the biases as (1, m)
    rows, so every block is 2-D (Mosaic refuses 1-D blocks that are a strict
    part of their array)."""
    b, d = dx.shape
    m = h.shape[1]
    bb = min(block_b, b)
    assert b % bb == 0
    nb = b // bb
    w_x = jnp.concatenate([p["w_g"], p["w_r"], p["w_h"]], axis=1)   # (d, 3m)
    u_gr = jnp.concatenate([p["u_g"], p["u_r"]], axis=1)            # (m, 2m)
    row = lambda x: jnp.reshape(x, (1, -1))

    full = lambda shape: pl.BlockSpec(shape, lambda bi: (0, 0))
    col = lambda: pl.BlockSpec((bb, 1), lambda bi: (bi, 0))
    out = pl.pallas_call(
        functools.partial(_gate_kernel, m=m),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, d), lambda bi: (bi, 0)),
            pl.BlockSpec((bb, m), lambda bi: (bi, 0)),
            col(),
            full((d, 3 * m)), full((m, 2 * m)), full((1, m)), full((1, 1)),
            full((1, m)),
            full((m, m)), full((1, m)),
            full((m, 1)), full((1, 1)),
        ],
        out_specs=[
            pl.BlockSpec((bb, m), lambda bi: (bi, 0)),
            col(),
            col(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        dx, h, vol,
        w_x, u_gr, row(p["b_g"]), row(p["alpha"]),
        row(p["b_r"]),
        p["u_h"], row(p["b_h"]),
        p["w_o"], row(p["b_o"]),
    )
    return out
