"""Plain reference of Moonlight-16B-A3B (the DeepSeek-V3 architecture),
written from its published configuration and independent of the program.

Per layer: x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)), where FFN is a dense
SwiGLU in the first ``first_k_dense_replace`` layers and the expert layer
in the rest.

Latent attention (``q_lora_rank`` null): q = x W_q split per head into
``qk_nope_head_dim`` and ``qk_rope_head_dim`` parts; [c_kv, k_pe] =
x W_kv_a, c_kv through its own RMSNorm; per head [k_nope, v] = c_kv W_kv_b;
k_pe is one rotary key shared by every head.  RoPE (base ``rope_theta``, no
scaling) acts on the rotary parts in DeepSeek-V3's published layout: the
interleaved pairs are permuted to halves (even dimensions first), then
rotated in rotate-half form.  Scores are q_nope·k_nope + q_pe·k_pe over
sqrt(qk_nope + qk_rope), causal softmax, values v, output W_o.

Expert layer: router logits in float32, sigmoid scores; the top
``num_experts_per_tok`` of (score + ``e_score_correction_bias``) are chosen
(``n_group`` = ``topk_group`` = 1, so no group step), and weighted by
their unbiased scores, normalised over the chosen (``norm_topk_prob``)
and scaled by ``routed_scaling_factor``.  Each expert is a SwiGLU of
``moe_intermediate_size``; the shared experts, one SwiGLU of
``n_shared_experts * moe_intermediate_size``, are added for every token.

The full forward runs over a whole sequence at once in float32 with
``Precision.HIGHEST`` matrix products.  ``fp8=True`` is the control: every
matrix product takes e4m3 operands (per-output-channel weight scales,
per-row activation scales) with float32 accumulation.

Departures from the published model, shared with the program:

- the chip's share under expert parallelism: of the ``router_experts``
  (64) experts the router scores, this chip holds ``n_routed_experts``
  starting at ``expert_offset``; only those add their part, the absent
  experts' part is left out (the other chips of the layer compute it);
- depth: the first ``num_hidden_layers`` of the published 27;
- weights are drawn, not loaded: every matrix normal(0,
  ``initializer_range``), norms 1, and ``e_score_correction_bias`` (a
  trained value in the checkpoint) normal(0, ``score_bias_std``); one key
  per leaf, split in the order of the parameter tree, in one jitted call,
  each value rounded to bfloat16 (the checkpoint's type), so that it is
  held exactly in whatever float type the served pool keeps it in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def leaf_specs(c: dict) -> dict:
    """{path: (shape, init)} of the parameter tree, nested as the served
    model nests it: a segment of the dense layers, then one of the expert
    layers, each stacked on a leading layer axis."""
    d, h, v = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    r, dn, dr, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    f, fe = c["intermediate_size"], c["moe_intermediate_size"]
    held, e = c["n_routed_experts"], c["router_experts"]
    fs = c["n_shared_experts"] * fe
    k = c["first_k_dense_replace"]
    n = c["num_hidden_layers"] - k

    def attn(m):
        return {"wq": ((m, d, h * (dn + dr)), "normal"),
                "wkv_a": ((m, d, r + dr), "normal"),
                "kv_norm": {"scale": ((m, r), "ones")},
                "wkv_b": ((m, r, h * (dn + dv)), "normal"),
                "wo": ((m, h * dv, d), "normal")}

    def swiglu(*lead, width):
        return {"w_gate": ((*lead, d, width), "normal"),
                "w_up": ((*lead, d, width), "normal"),
                "w_down": ((*lead, width, d), "normal")}

    def layer(m, mlp):
        return {"attn": attn(m), "mlp": mlp,
                "norm1": {"scale": ((m, d), "ones")},
                "norm2": {"scale": ((m, d), "ones")}}

    experts = dict(swiglu(n, held, width=fe),
                   router=((n, d, e), "normal"),
                   router_bias=((n, e), "bias"),
                   shared=swiglu(n, width=fs))
    return {"embed": {"tok": ((v, d), "normal"), "out": ((d, v), "normal")},
            "final_norm": {"scale": ((d,), "ones")},
            "segments": [{"pos0": layer(k, swiglu(k, width=f))},
                         {"pos0": layer(n, experts)}]}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


@partial(jax.jit, static_argnames=("c", "dtypes"))
def _draw(key, *, c, dtypes):
    c = dict(c)
    leaves, tree = jax.tree_util.tree_flatten(leaf_specs(c), is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    std = {"normal": float(c["initializer_range"]),
           "bias": float(c["score_bias_std"])}
    out = []
    for (shape, init), k, dt in zip(leaves, keys, dtypes):
        if init == "ones":
            w = jnp.ones(shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) * std[init]
        out.append(w.astype(jnp.bfloat16).astype(dt))
    return jax.tree_util.tree_unflatten(tree, out)


def weight_shapes(c: tuple):
    """The parameter tree's ``jax.ShapeDtypeStruct``s in float32."""
    return jax.eval_shape(partial(make_weights, c), jax.random.PRNGKey(0))


def make_weights(c: tuple, key, dtypes=None):
    """The parameter tree from ``key``, on the device, in one jitted call:
    float32 leaves, or each leaf in its type from ``dtypes`` (a pytree of
    dtypes of the same structure).  ``c`` is the model's configuration as
    a hashable tuple of items."""
    n = len(jax.tree_util.tree_leaves(leaf_specs(dict(c)), is_leaf=_is_spec))
    if dtypes is None:
        flat = (jnp.dtype(jnp.float32),) * n
    else:
        flat = tuple(jnp.dtype(d) for d in jax.tree_util.tree_leaves(dtypes))
    return _draw(key, c=c, dtypes=flat)


def _q8(x, axis):
    """e4m3 values and scales of x, scaled so the largest |x| along ``axis``
    lands on the format's largest finite value."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8), s


def _mm(x, w, fp8: bool):
    """x (..., k) @ w (k, n) in float32, or with e4m3 operands."""
    if not fp8:
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, xs = _q8(x, -1)
    wq, ws = _q8(w, 0)
    y = jnp.matmul(xq.astype(jnp.float32), wq.astype(jnp.float32),
                   precision=HIGHEST)
    return y * xs * ws


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x: (B, S, H, D), positions 0..S-1: interleaved pairs permuted to
    halves, then rotate-half."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    s, dim = x.shape[1], x.shape[-1]
    half = dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]      # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(y, m, fp8):
    return _mm(jax.nn.silu(_mm(y, m["w_gate"], fp8)) * _mm(y, m["w_up"], fp8),
               m["w_down"], fp8)


def experts(c, m, y, fp8: bool = False):
    """The expert layer's output on y (T, d): the held experts' part plus
    the shared experts."""
    e0, held = c["expert_offset"], c["n_routed_experts"]
    logits = _mm(y, m["router"], fp8)
    scores = jax.nn.sigmoid(logits)
    _, top = jax.lax.top_k(scores + m["router_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, top, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * c["routed_scaling_factor"]
    # gate (T, held): each held expert's weight for each token, 0 unchosen
    ids = jnp.arange(e0, e0 + held)
    gate = jnp.sum(jnp.where(top[:, :, None] == ids, w[:, :, None], 0.0), 1)
    out = jax.vmap(lambda wg, wu, wd: _swiglu(
        y, {"w_gate": wg, "w_up": wu, "w_down": wd}, fp8))(
        m["w_gate"], m["w_up"], m["w_down"])                       # (held, T, d)
    return jnp.einsum("te,etd->td", gate, out, precision=HIGHEST) \
        + _swiglu(y, m["shared"], fp8)


@partial(jax.jit, static_argnames=("c", "fp8"))
def logits(c, params, tokens, pick, *, fp8: bool = False):
    """(B, S) tokens -> (B, P, V) float32 logits of the full forward pass at
    the (B, P) positions ``pick``.  Rows may be right-padded: a causal
    model's logits at a position never see what follows it.  ``c`` is the
    model's configuration as a hashable tuple of items."""
    c = dict(c)
    h, r, dn, dr, dv = (c["num_attention_heads"], c["kv_lora_rank"],
                        c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                        c["v_head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    b, s = tokens.shape
    x = params["embed"]["tok"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def attention(x, a):
        q = _mm(x, a["wq"], fp8).reshape(b, s, h, dn + dr)
        kv_a = _mm(x, a["wkv_a"], fp8)
        c_kv = _rms(kv_a[..., :r], a["kv_norm"]["scale"], eps)
        k_pe = _rope(kv_a[..., None, r:], theta)                   # (B, S, 1, dr)
        kv = _mm(c_kv, a["wkv_b"], fp8).reshape(b, s, h, dn + dv)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))], -1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            * (dn + dr) ** -0.5
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, kv[..., dn:], precision=HIGHEST)
        return _mm(o.reshape(b, s, h * dv), a["wo"], fp8)

    def dense(x, p):
        x = x + attention(_rms(x, p["norm1"]["scale"], eps), p["attn"])
        return x + _swiglu(_rms(x, p["norm2"]["scale"], eps), p["mlp"], fp8), None

    def moe(x, p):
        x = x + attention(_rms(x, p["norm1"]["scale"], eps), p["attn"])
        y = _rms(x, p["norm2"]["scale"], eps).reshape(b * s, -1)
        return x + experts(c, p["mlp"], y, fp8).reshape(x.shape), None

    x, _ = jax.lax.scan(dense, x, params["segments"][0]["pos0"])
    x, _ = jax.lax.scan(moe, x, params["segments"][1]["pos0"])
    x = jnp.take_along_axis(x, pick[:, :, None], axis=1)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return _mm(x, params["embed"]["out"], fp8)
