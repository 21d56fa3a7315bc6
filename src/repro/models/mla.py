"""Multi-head latent attention (DeepSeek-V2/V3, Moonlight), scope ``mla.attend``.

Queries are projected directly (``q_lora_rank`` null): q = x W_q, per head
a ``qk_nope_head_dim`` part and a ``qk_rope_head_dim`` part.  Keys and
values come from a latent shared by every head: [c_kv, k_pe] = x W_kv_a,
c_kv normalised by its own RMSNorm (``kv_norm``), k_pe one rotary key per
position; per head, [k_nope, v] = c_kv W_kv_b.  Scores are
q_nope·k_nope + q_pe·k_pe over sqrt(qk_nope + qk_rope).

RoPE follows the published DeepSeek-V3 layout: the rotary part's
interleaved pairs are permuted to halves (even dims first), then rotated in
rotate-half form.

Prefill and training run the expanded form (per-head keys and values).
Decode runs the absorbed form over a latent cache that holds only c_kv and
the rotated k_pe per position, shared by every head: W_kv_b's key half is
folded into the query (q_lat = q_nope W_uk^T) and its value half applied
after the weighted sum over the latents.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.attention import NEG_INF, chunked_attention
from repro.models.config import ModelConfig
from repro.models.layers import Ctx, apply_rope, rmsnorm
from repro.models.params import ParamSpec


def mla_specs(cfg: ModelConfig) -> dict:
    m, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    r = m.kv_lora_rank
    return {
        "wq": ParamSpec((d, h * m.qk_head_dim), ("embed", "heads_flat"), stddev=d ** -0.5),
        "wkv_a": ParamSpec((d, r + m.qk_rope_head_dim), ("embed", None), stddev=d ** -0.5),
        "kv_norm": {"scale": ParamSpec((r,), (None,), init="ones")},
        "wkv_b": ParamSpec((r, h * (m.qk_nope_head_dim + m.v_head_dim)), (None, "heads_flat"),
                           stddev=r ** -0.5),
        "wo": ParamSpec((h * m.v_head_dim, d), ("heads_flat", "embed"),
                        stddev=(h * m.v_head_dim) ** -0.5 / math.sqrt(2 * cfg.num_layers)),
    }


def mla_cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    """The latent cache: c_kv and the rotated k_pe per position."""
    dt = jnp.dtype(cfg.compute_dtype)
    ax = ("cache_batch", "cache_seq", None)
    return {
        "ckv": ParamSpec((batch, cache_len, cfg.mla.kv_lora_rank), ax, dtype=dt, init="zeros"),
        "kpe": ParamSpec((batch, cache_len, cfg.mla.qk_rope_head_dim), ax, dtype=dt, init="zeros"),
    }


def rope_interleaved(x, positions, theta: float):
    """DeepSeek-V3's rotary layout: (..., S, H, D) with interleaved pairs
    permuted to halves, then rotate-half RoPE."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def _write(cache, new, idx):
    """Write one position per row at ``idx`` (scalar or (B,))."""
    if jnp.ndim(idx) == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, idx, axis=1)
    hit = jnp.arange(cache.shape[1])[None, :] == idx[:, None]   # (B, C)
    return jnp.where(hit[:, :, None], new, cache)


def mla_forward(
    ctx: Ctx,
    p,
    x,
    *,
    positions,          # (B, S) int32
    cache=None,         # dict(ckv, kpe, length) or None
    cache_out_len: Optional[int] = None,  # prefill: emit a cache of this length
):
    with jax.named_scope("mla.attend"):
        return _mla(ctx, p, x, positions, cache, cache_out_len)


def _mla(ctx, p, x, positions, cache, cache_out_len):
    cfg, m = ctx.cfg, ctx.cfg.mla
    dt = ctx.compute_dtype
    b, s, _ = x.shape
    h, dn, dr, dv, r = (cfg.num_heads, m.qk_nope_head_dim, m.qk_rope_head_dim,
                        m.v_head_dim, m.kv_lora_rank)
    scale = m.qk_head_dim ** -0.5

    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)).reshape(b, s, h, dn + dr)
    q_nope = q[..., :dn]
    q_pe = rope_interleaved(q[..., dn:], positions, cfg.rope_theta)
    kv_a = jnp.einsum("bsd,de->bse", x, p["wkv_a"].astype(dt))
    c_kv = rmsnorm(p["kv_norm"], kv_a[..., :r], cfg.norm_eps)
    k_pe = rope_interleaved(kv_a[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    wkv_b = p["wkv_b"].astype(dt).reshape(r, h, dn + dv)

    new_cache = None
    if ctx.mode == "decode":
        idx = cache["length"]
        ckv = ctx.constrain(_write(cache["ckv"], c_kv, idx), "cache_batch", "cache_seq", None)
        kpe = ctx.constrain(_write(cache["kpe"], k_pe, idx), "cache_batch", "cache_seq", None)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wkv_b[..., :dn])
        sc = (jnp.einsum("bshr,bcr->bhsc", q_lat, ckv, preferred_element_type=jnp.float32)
              + jnp.einsum("bshp,bcp->bhsc", q_pe, kpe, preferred_element_type=jnp.float32))
        lengths = jnp.broadcast_to(jnp.atleast_1d(idx + 1), (b,))
        valid = jnp.arange(ckv.shape[1])[None, :] < lengths[:, None]
        sc = jnp.where(valid[:, None, None, :], sc * scale, NEG_INF)
        w = jax.nn.softmax(sc, axis=-1).astype(dt)
        o_lat = jnp.einsum("bhsc,bcr->bshr", w, ckv)
        out = jnp.einsum("bshr,rhv->bshv", o_lat, wkv_b[..., dn:])
        new_cache = {"ckv": ckv, "kpe": kpe, "length": idx + 1}
    else:
        kv = jnp.einsum("bsr,rhe->bshe", c_kv, wkv_b)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (b, s, h, dr))], axis=-1)
        qf = jnp.concatenate([q_nope, q_pe], axis=-1)
        # chunked_attention takes one head size: pad v up to the q/k size
        v = jnp.pad(kv[..., dn:], ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
        out = chunked_attention(qf, k, v, positions, q_chunk=cfg.attn_chunk,
                                k_chunk=cfg.attn_chunk)[..., :dv]
        if cache_out_len is not None:
            keep = min(cache_out_len, s)
            pad = [(0, 0), (0, cache_out_len - keep), (0, 0)]
            new_cache = {
                "ckv": jnp.pad(c_kv[:, s - keep:], pad),
                "kpe": jnp.pad(k_pe[:, s - keep:], pad),
                "length": jnp.asarray(s, jnp.int32),
            }

    y = jnp.einsum("bse,ed->bsd", out.reshape(b, s, h * dv), p["wo"].astype(dt))
    return y, new_cache
