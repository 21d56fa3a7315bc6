"""Decoder block assembly: attn / mla / rglru / ssm mixers + (optional) MLP/MoE."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.models.attention import attn_forward, attn_specs
from repro.models.config import ModelConfig
from repro.models.layers import Ctx, rmsnorm, rmsnorm_specs
from repro.models.mla import mla_cache_specs, mla_forward, mla_specs
from repro.models.mlp import mlp_forward, mlp_specs
from repro.models.moe import moe_forward, moe_specs
from repro.models.params import ParamSpec
from repro.models.rglru import rglru_forward, rglru_specs
from repro.models.ssm import ssm_forward, ssm_specs


def block_specs(cfg: ModelConfig, kind: str, serve: bool = False,
                dense: bool = False) -> dict:
    """``dense``: a dense MLP even where the model has experts (its leading
    ``first_k_dense`` layers)."""
    specs = {"norm1": rmsnorm_specs(cfg.d_model)}
    if kind == "attn":
        specs["attn"] = attn_specs(cfg)
    elif kind == "mla":
        specs["attn"] = mla_specs(cfg)
    elif kind == "rglru":
        specs["rglru"] = rglru_specs(cfg)
    elif kind == "ssm":
        specs["ssm"] = ssm_specs(cfg)
    else:
        raise ValueError(kind)
    if kind != "ssm":
        specs["norm2"] = rmsnorm_specs(cfg.d_model)
        if cfg.moe is not None and not dense:
            specs["mlp"] = moe_specs(cfg, quantized=serve and cfg.quant_experts_serve)
        else:
            specs["mlp"] = mlp_specs(cfg)
    return specs


def attn_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.attn_window is not None:
        return min(cfg.attn_window, seq_len)  # rolling window cache
    return seq_len + cfg.decode_headroom


def block_cache_specs(cfg: ModelConfig, kind: str, batch: int, seq_len: int) -> dict:
    """Cache layout per layer (as ParamSpec so dry-run can use ShapeDtypeStruct)."""
    dt = jnp.dtype(cfg.compute_dtype)
    if kind == "attn":
        c = attn_cache_len(cfg, seq_len)
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        ax = ("cache_batch", "cache_seq", "cache_kv", "cache_dim")
        return {
            "k": ParamSpec((batch, c, kv, hd), ax, dtype=dt, init="zeros"),
            "v": ParamSpec((batch, c, kv, hd), ax, dtype=dt, init="zeros"),
        }
    if kind == "mla":
        return mla_cache_specs(cfg, batch, attn_cache_len(cfg, seq_len))
    if kind == "ssm":
        di, st, cw = cfg.d_inner, cfg.ssm.d_state, cfg.ssm.d_conv
        return {
            "conv": ParamSpec((batch, cw - 1, di), ("cache_batch", None, "inner"), dtype=dt, init="zeros"),
            "h": ParamSpec((batch, di, st), ("cache_batch", "inner", "state"), dtype=jnp.float32, init="zeros"),
        }
    if kind == "rglru":
        w, cw = cfg.lru_width, cfg.rglru.conv_width
        return {
            "conv": ParamSpec((batch, cw - 1, w), ("cache_batch", None, "rglru_width"), dtype=dt, init="zeros"),
            "h": ParamSpec((batch, w), ("cache_batch", "rglru_width"), dtype=jnp.float32, init="zeros"),
        }
    raise ValueError(kind)


def block_apply(
    ctx: Ctx,
    kind: str,
    p: dict,
    x,
    *,
    positions=None,
    length=None,
    cache: Optional[dict] = None,
    emit_cache: bool = False,
    dense: bool = False,
):
    """Returns (x, new_cache_or_None, aux_loss, expert counts): the counts
    are (2,) int32, the rows the held experts computed and the held experts
    that got any (zero without experts)."""
    cfg = ctx.cfg
    aux = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((2,), jnp.int32)
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)

    if kind in ("attn", "mla"):
        c = dict(cache, length=length) if cache is not None else None
        out_len = attn_cache_len(cfg, x.shape[1]) if emit_cache else None
        fwd = attn_forward if kind == "attn" else mla_forward
        y, new_cache = fwd(ctx, p["attn"], h, positions=positions, cache=c, cache_out_len=out_len)
    elif kind == "rglru":
        c = dict(cache, length=length) if cache is not None else None
        y, new_cache = rglru_forward(ctx, p["rglru"], h, cache=c)
    elif kind == "ssm":
        c = dict(cache, length=length) if cache is not None else None
        y, new_cache = ssm_forward(ctx, p["ssm"], h, cache=c)
    else:
        raise ValueError(kind)

    if new_cache is not None:
        new_cache.pop("length", None)
    x = x + y

    if kind != "ssm":
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if cfg.moe is not None and not dense:
            y2, aux, counts = moe_forward(ctx, p["mlp"], h2)
        else:
            y2 = mlp_forward(ctx, p["mlp"], h2, activation=cfg.mlp_activation)
        x = x + y2

    return x, new_cache, aux, counts
