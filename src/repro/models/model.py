"""Top-level model: segment-scanned decoder with train / prefill / decode paths.

Layers are grouped into *segments*: the repeating ``layer_pattern`` unit is
stacked ``n_repeat`` times and driven by ``jax.lax.scan`` (one compiled body
per segment — essential to keep HLO size and CPU compile time bounded for the
512-device dry-run).  A trailing remainder (e.g. recurrentgemma's 38 = 12x3+2)
forms a second, shorter segment, and leading dense layers of an MoE model
(``first_k_dense``) a segment of their own ahead of the pattern.

A model with experts keeps two counters in its decode cache (``counters``):
the rows its held experts computed and the held experts that got any,
summed over decode steps and layers on the device.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.blocks import block_apply, block_cache_specs, block_specs
from repro.models.config import ModelConfig
from repro.models.layers import Ctx, embed_specs, embed_tokens, output_weights, rmsnorm, rmsnorm_specs
from repro.models.params import ParamSpec, tree_map_specs


#: the decode cache's expert counters, in the order block_apply counts them
EXPERT_COUNTERS = ("expert_rows", "experts_hit")


# ---------------------------------------------------------------------------
# Segments
# ---------------------------------------------------------------------------
def build_segments(cfg: ModelConfig) -> list[tuple[tuple[str, ...], int, bool]]:
    """[(pattern, repeats, dense)]: ``dense`` segments carry a dense MLP
    where the model has experts."""
    k = cfg.first_k_dense if cfg.moe is not None else 0
    segs: list[tuple[tuple[str, ...], int, bool]] = []
    if k:
        segs.append((tuple(cfg.layer_kind(i) for i in range(k)), 1, True))
    m = len(cfg.layer_pattern)
    pattern = tuple(cfg.layer_kind(k + i) for i in range(m))
    full, rem = divmod(cfg.num_layers - k, m)
    if full:
        segs.append((pattern, full, False))
    if rem:
        segs.append((pattern[:rem], 1, False))
    return segs


def _stack_specs(specs: dict, n: int) -> dict:
    return tree_map_specs(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.dtype, s.init, s.stddev),
        specs,
    )


def model_specs(cfg: ModelConfig, serve: bool = False) -> dict:
    segments = []
    for pattern, n, dense in build_segments(cfg):
        seg = {
            f"pos{i}": _stack_specs(block_specs(cfg, kind, serve=serve, dense=dense), n)
            for i, kind in enumerate(pattern)
        }
        segments.append(seg)
    return {
        "embed": embed_specs(cfg),
        "segments": segments,
        "final_norm": rmsnorm_specs(cfg.d_model),
    }


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    segments = []
    for pattern, n, _ in build_segments(cfg):
        seg = {
            f"pos{i}": _stack_specs(block_cache_specs(cfg, kind, batch, seq_len), n)
            for i, kind in enumerate(pattern)
        }
        segments.append(seg)
    out = {
        "length": ParamSpec((), (), dtype=jnp.int32, init="zeros"),
        "segments": segments,
    }
    if cfg.moe is not None:
        out["counters"] = {
            name: ParamSpec((), (), dtype=jnp.int32, init="zeros")
            for name in EXPERT_COUNTERS
        }
    return out


# ---------------------------------------------------------------------------
# Backbone forward
# ---------------------------------------------------------------------------
def _segment_forward(ctx: Ctx, pattern, seg_params, x, *, positions, length, seg_cache,
                     emit_cache, dense=False):
    cfg = ctx.cfg

    def body(x_carry, xs):
        layer_p, layer_c = xs
        new_c = {}
        aux_total = jnp.zeros((), jnp.float32)
        counts_total = jnp.zeros((2,), jnp.int32)
        for i, kind in enumerate(pattern):
            c = layer_c[f"pos{i}"] if layer_c is not None else None
            x_carry, nc, aux, counts = block_apply(
                ctx, kind, layer_p[f"pos{i}"], x_carry,
                positions=positions, length=length, cache=c, emit_cache=emit_cache,
                dense=dense,
            )
            if nc is not None:
                new_c[f"pos{i}"] = nc
            aux_total = aux_total + aux
            counts_total = counts_total + counts
        x_carry = ctx.constrain(x_carry, "batch", "act_seq_sp", "act_embed")
        return x_carry, (new_c, aux_total, counts_total)

    if cfg.remat and ctx.mode == "train":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    xs = (seg_params, seg_cache)
    x, (new_cache, aux, counts) = jax.lax.scan(body, x, xs)
    if not new_cache:
        new_cache = None
    return x, new_cache, jnp.sum(aux), jnp.sum(counts, axis=0)


def forward(
    ctx: Ctx,
    params: dict,
    inputs: dict,
    *,
    cache: Optional[dict] = None,
    emit_cache: bool = False,
):
    """inputs: {"tokens": (B,S)} or {"embeddings": (B,S,d)}; optional
    {"positions": (B,S) or (B,3,S)}.  Returns (hidden (B,S,d), new_cache, aux)."""
    cfg = ctx.cfg
    dt = ctx.compute_dtype

    if cfg.embed_inputs:
        x = embed_tokens(ctx, params["embed"], inputs["tokens"])
        if cfg.family == "hybrid":  # gemma-style embedding scale
            x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
        b, s = inputs["tokens"].shape
    else:
        x = inputs["embeddings"].astype(dt)
        x = ctx.constrain(x, "batch", "act_seq", "act_embed")
        b, s = x.shape[0], x.shape[1]

    length = cache["length"] if cache is not None else None
    if "positions" in inputs:
        positions = inputs["positions"]
    elif ctx.mode == "decode":
        # scalar length = whole-batch progress; (B,) = per-row slab progress
        pos = length[None, None] if length.ndim == 0 else length[:, None]
        positions = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[:, None, :], (b, 3, 1))
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[:, None, :], (b, 3, s))

    new_segments = []
    aux_total = jnp.zeros((), jnp.float32)
    counts_total = jnp.zeros((2,), jnp.int32)
    for seg_idx, (pattern, n, dense) in enumerate(build_segments(cfg)):
        seg_params = params["segments"][seg_idx]
        seg_cache = cache["segments"][seg_idx] if cache is not None else None
        x, new_seg, aux, counts = _segment_forward(
            ctx, pattern, seg_params, x,
            positions=positions, length=length, seg_cache=seg_cache, emit_cache=emit_cache,
            dense=dense,
        )
        new_segments.append(new_seg)
        aux_total = aux_total + aux
        counts_total = counts_total + counts

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)

    new_cache = None
    if any(s is not None for s in new_segments):
        new_len = (length + s) if length is not None else jnp.asarray(s, jnp.int32)
        new_cache = {"length": new_len, "segments": new_segments}
        if cache is not None and "counters" in cache:
            new_cache["counters"] = {
                name: cache["counters"][name] + counts_total[i]
                for i, name in enumerate(EXPERT_COUNTERS)
            }
    return x, new_cache, aux_total


# ---------------------------------------------------------------------------
# Losses / heads
# ---------------------------------------------------------------------------
def chunked_ce_loss(ctx: Ctx, x, w_out, labels, mask=None):
    """Fused lm-head + cross-entropy, scanned over sequence chunks so the
    (B, chunk, V) logits buffer stays bounded and vocab-sharded."""
    cfg = ctx.cfg
    b, s, d = x.shape
    chunk = min(cfg.loss_chunk, s)
    nc = s // chunk
    assert s % chunk == 0, (s, chunk)
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)

    xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    mc = mask.reshape(b, nc, chunk).transpose(1, 0, 2)
    w = w_out.astype(ctx.compute_dtype)

    def body(carry, xs):
        x_blk, l_blk, m_blk = xs
        logits = jnp.einsum("bcd,dv->bcv", x_blk, w)
        logits = ctx.constrain(logits, "batch", None, "vocab").astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, l_blk[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * m_blk
        return (carry[0] + nll.sum(), carry[1] + m_blk.sum()), None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (total, denom), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (xc, lc, mc))
    return total / jnp.maximum(denom, 1.0)


def logits_last(ctx: Ctx, x_last, w_out):
    """x_last: (B, 1, d) -> (B, V) float32 logits."""
    logits = jnp.einsum("bod,dv->bov", x_last, w_out.astype(ctx.compute_dtype))
    return ctx.constrain(logits[:, 0], "batch", "vocab").astype(jnp.float32)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def loss_fn(ctx: Ctx, params, batch, aux_weight: float = 0.01):
    x, _, aux = forward(ctx, params, batch)
    w_out = output_weights(ctx.cfg, params["embed"])
    ce = chunked_ce_loss(ctx, x, w_out, batch["labels"], batch.get("mask"))
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


def prefill(ctx: Ctx, params, batch):
    ctx = dataclasses.replace(ctx, mode="prefill")
    x, cache, _ = forward(ctx, params, batch, emit_cache=True)
    w_out = output_weights(ctx.cfg, params["embed"])
    return logits_last(ctx, x[:, -1:], w_out), cache


def decode_step(ctx: Ctx, params, cache, batch):
    ctx = dataclasses.replace(ctx, mode="decode")
    x, new_cache, _ = forward(ctx, params, batch, cache=cache)
    w_out = output_weights(ctx.cfg, params["embed"])
    return logits_last(ctx, x, w_out), new_cache
