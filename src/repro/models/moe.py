"""Token-choice top-k MoE (EP-shardable), with optional shared experts.

Routing (scope ``moe.route``) is Mixtral's softmax over the chosen logits, or
DeepSeek-V3's sigmoid scores (router logits in float32) chosen with a
selection-only bias (``e_score_correction_bias``): the weights are the
unbiased scores of the chosen experts, normalised over them and scaled by
``routed_scale``.

The layer holds experts ``[expert_offset, expert_offset + n_held)`` of
``num_experts`` -- the chip's share under expert parallelism (all of them by
default).  It routes over every expert and adds its own experts' part of
the result; slots routed to experts held elsewhere add nothing here.

Expert compute (scope ``moe.experts``):

* training -- capacity-bounded gather dispatch:
  1. position-in-expert via cumsum over the flattened (token*k) assignment
  2. tokens above capacity C = ceil(T*k/E * capacity_factor) are dropped
  3. gather to (E, C, d), grouped einsum against (E, d, f) expert weights,
     scatter-gather back with combine weights.
* serving (prefill, decode) -- dropless: the (token, choice) slots of held
  experts are sorted by expert and run through ``jax.lax.ragged_dot``, so
  every routed slot is computed, however uneven the routing.  It also
  counts the rows its experts computed and the experts that got any.

Shared experts (scope ``moe.shared``), one SwiGLU of ``n_shared * d_expert``,
are added for every token.

Expert weight dim 0 is the "experts" logical axis (EP over the model mesh
axis); the d_model dim carries "expert_in" so memory-constrained serving
configs (mixtral decode) can FSDP-shard expert weights over "data".
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import Ctx
from repro.models.mlp import mlp_forward, mlp_specs
from repro.models.params import ParamSpec

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_specs(cfg: ModelConfig, quantized: bool = False) -> dict:
    e = cfg.moe
    d, f, n = cfg.d_model, e.d_expert, e.held
    s_in = d ** -0.5
    s_out = f ** -0.5 / math.sqrt(2 * cfg.num_layers)
    wdt = jnp.int8 if quantized else jnp.float32
    specs = {
        "router": ParamSpec((d, e.num_experts), ("embed", None), stddev=s_in),
        "w_gate": ParamSpec((n, d, f), ("experts", "expert_in", "expert_mlp"), dtype=wdt, stddev=s_in),
        "w_up": ParamSpec((n, d, f), ("experts", "expert_in", "expert_mlp"), dtype=wdt, stddev=s_in),
        "w_down": ParamSpec((n, f, d), ("experts", "expert_mlp", "expert_in"), dtype=wdt, stddev=s_out),
    }
    if e.scoring == "sigmoid":
        specs["router_bias"] = ParamSpec((e.num_experts,), (None,), init="zeros")
    if e.n_shared:
        specs["shared"] = mlp_specs(cfg, width=e.n_shared * f)
    if quantized:
        for name in _EXPERT_WEIGHTS:
            specs[name + "_scale"] = ParamSpec(
                (n, 1, 1), ("experts", None, None), init="ones"
            )
    return specs


def quantize_expert_params(p: dict) -> dict:
    """fp32/bf16 expert weights -> int8 + per-expert absmax scales."""
    out = dict(p)
    for name in _EXPERT_WEIGHTS:
        w = jnp.asarray(p[name], jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=(1, 2), keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-12)
        out[name] = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        out[name + "_scale"] = scale.astype(jnp.float32)
    return out


def _expert_w(p: dict, name: str, dt):
    w = p[name]
    if w.dtype == jnp.int8:
        return (w.astype(dt) * p[name + "_scale"].astype(dt))
    return w.astype(dt)


def route(cfg: ModelConfig, p, x, dt):
    """x (..., d) -> (weights (..., k) float32, expert ids (..., k), router
    logits (..., E) float32)."""
    e = cfg.moe
    if e.scoring == "sigmoid":
        logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32), e.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * e.routed_scale
    else:
        logits = jnp.einsum("...d,de->...e", x, p["router"].astype(dt)).astype(jnp.float32)
        w, ids = jax.lax.top_k(logits, e.top_k)
        w = jax.nn.softmax(w, axis=-1)
    return w, ids, logits


def moe_forward(ctx: Ctx, p, x):
    """Returns (y, load-balance loss, counts): counts is (2,) int32, the
    rows the held experts computed and the held experts that got any (zero
    in training)."""
    cfg = ctx.cfg
    e = cfg.moe
    dt = ctx.compute_dtype
    b, s, d = x.shape
    t = b * s

    with jax.named_scope("moe.route"):
        w, ids, logits = route(cfg, p, x.reshape(t, d), dt)
    with jax.named_scope("moe.experts"):
        if ctx.mode == "train":
            y = _capacity_dispatch(ctx, p, x.reshape(t, d), w, ids)
            counts = jnp.zeros((2,), jnp.int32)
        else:
            y, counts = _dropless(ctx, p, x.reshape(t, d), w, ids)
    y = y.reshape(b, s, d)
    if e.n_shared:
        with jax.named_scope("moe.shared"):
            y = y + mlp_forward(ctx, p["shared"], x)
    aux = _load_balance_loss(logits, ids, e.num_experts)
    return y, aux, counts


def _held(cfg: ModelConfig, flat_ids):
    """(local expert index, held here) of global expert ids."""
    e = cfg.moe
    local = flat_ids - e.expert_offset
    return local, (local >= 0) & (local < e.held)


def _dropless(ctx: Ctx, p, xt, w, ids):
    """Every slot of a held expert computed: xt (t, d), w/ids (t, k)."""
    dt = ctx.compute_dtype
    n = ctx.cfg.moe.held
    t, k = ids.shape
    local, held = _held(ctx.cfg, ids.reshape(t * k))
    key = jnp.where(held, local, n)              # slots held elsewhere sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    xs = xt[order // k]                          # (t*k, d), grouped by expert

    def mm(a, name):
        return jax.lax.ragged_dot(a, _expert_w(p, name, dt), sizes,
                                  preferred_element_type=jnp.float32)

    h = (jax.nn.silu(mm(xs, "w_gate")) * mm(xs, "w_up")).astype(dt)
    ys = mm(h, "w_down")                         # rows past sum(sizes): not computed
    ws = w.reshape(t * k)[order]
    ys = jnp.where(held[order][:, None], ys * ws[:, None], 0.0)
    y = ys[jnp.argsort(order)].reshape(t, k, -1).sum(axis=1)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(jnp.int32)
    return y.astype(dt), counts


def _capacity_dispatch(ctx: Ctx, p, xt, w, ids):
    """Grouped (per-data-shard) dispatch: tokens are viewed as (G, t/G) with
    G = the DP shard count, and every dispatch op (cumsum, scatter, gather)
    is per-group — GSPMD keeps them local to the shard.  A single global
    dispatch instead forces an all-reduce of the full (E, cap, d) gathered
    tensor (measured 3.6 TB/layer on mixtral train — EXPERIMENTS.md §Perf
    iteration 2).  Capacity is per-group, like per-device capacity in
    production MoE stacks."""
    cfg = ctx.cfg
    e = cfg.moe
    dt = ctx.compute_dtype
    t, d = xt.shape
    k = e.top_k
    E = e.held

    # group count = DP shard count (1 on a single host)
    gcount = 1
    if ctx.rules is not None:
        for ax in ("pod", "data"):
            gcount *= ctx.rules.mesh_sizes.get(ax, 1)
    while t % gcount != 0:
        gcount //= 2
    tg = t // gcount
    cap = int(math.ceil(tg * k / e.num_experts * e.capacity_factor))
    cap = min(max(cap, e.min_capacity), tg * k)

    xt = xt.reshape(gcount, tg, d)
    xt = ctx.constrain(xt, "batch", None, "act_embed")

    local, held = _held(cfg, ids.reshape(gcount, tg * k))   # expert per slot
    flat_w = w.reshape(gcount, tg * k)
    onehot = jax.nn.one_hot(local, E, dtype=jnp.int32)      # (G, tg*k, E); 0 if not held
    pos_in_exp = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1  # (G, tg*k)
    keep = held & (pos_in_exp < cap)
    flat_ids = jnp.where(held, local, 0)

    token_idx = jnp.broadcast_to(
        jnp.repeat(jnp.arange(tg), k)[None], (gcount, tg * k)
    )
    # scatter token indices into the (G, E, cap) dispatch table; dropped
    # slots write out-of-bounds and are discarded by mode="drop".  All
    # indexed ops are vmapped over G so they lower to *batched* gathers/
    # scatters, which GSPMD shards on the group dim (a flat 3D advanced
    # index loses that structure and replicates — §Perf iteration 3).
    upd_c = jnp.where(keep, pos_in_exp, cap)
    table = jax.vmap(
        lambda ids, c, tok: jnp.full((E, cap), tg, jnp.int32).at[ids, c].set(tok, mode="drop")
    )(flat_ids, upd_c, token_idx)

    x_pad = jnp.concatenate([xt, jnp.zeros((gcount, 1, d), xt.dtype)], axis=1)
    x_exp = jax.vmap(lambda xp, tbl: xp[tbl])(x_pad, table)  # (G, E, cap, d)
    x_exp = ctx.constrain(x_exp, "batch", "experts", None, "act_embed")

    g = jnp.einsum("gecd,edf->gecf", x_exp, _expert_w(p, "w_gate", dt))
    u = jnp.einsum("gecd,edf->gecf", x_exp, _expert_w(p, "w_up", dt))
    h = jax.nn.silu(g) * u
    h = ctx.constrain(h, "batch", "experts", None, "expert_mlp")
    y_exp = jnp.einsum("gecf,efd->gecd", h, _expert_w(p, "w_down", dt))  # (G, E, cap, d)

    # gather back per slot and combine with routing weights
    slot_e = jnp.where(keep, flat_ids, 0)
    slot_c = jnp.clip(pos_in_exp, 0, cap - 1)
    y_slots = jax.vmap(lambda ye, se, sc: ye[se, sc])(y_exp, slot_e, slot_c)
    y_slots = jnp.where(keep[..., None], y_slots, 0)              # (G, tg*k, d)
    y = jnp.sum(
        (y_slots * flat_w[..., None].astype(dt)).reshape(gcount, tg, k, d), axis=2
    )
    return y.reshape(t, d)


def _load_balance_loss(logits, ids, num_experts):
    """Switch-style auxiliary load-balancing loss."""
    probs = jax.nn.softmax(logits, axis=-1)                       # (t, E)
    density = jnp.mean(
        jax.nn.one_hot(ids[:, 0], num_experts, dtype=jnp.float32), axis=0
    )
    density_proxy = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(density * density_proxy)
