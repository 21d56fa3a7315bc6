"""Plain reference of the two served models (Qwen1.5 and Qwen3 decoders),
written from their published architecture and independent of the program.

Per layer: x += Attn(RMSNorm(x)); x += MLP(RMSNorm(x)).  Attention is causal
multi-head attention with grouped key/value heads, rotary position
embedding (rotate-half form, base ``rope_theta``) on q and k, an optional
q/k/v bias (Qwen1.5) and an optional RMSNorm over each q and k head before
the rotation (Qwen3).  The MLP is SwiGLU: down(silu(gate(x)) * up(x)).
Logits come from the final RMSNorm and the output projection, or the
transposed token embedding where the model ties them.

The full forward runs over a whole sequence at once in float32 with
``Precision.HIGHEST`` matrix products.  ``fp8=True`` is the control: every
matrix product takes e4m3 operands (per-output-channel weight scales,
per-row activation scales) with float32 accumulation.

Weights are the benchmark's data, drawn from the seed's key as the
published models initialise theirs: every matrix (embedding, projections,
the untied head) normal(0, ``initializer_range``), norms 1, biases 0; one
key per leaf, split in the order of the parameter tree, in one jitted
call.  Each value is rounded to bfloat16, the published checkpoints'
type, so that it is held exactly in whatever float type the served pool
keeps it in, and the reference computes on the same numbers.
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
    model nests it (one stacked layer segment)."""
    d, h, kv, hd = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"], c["head_dim"]
    f, n, v = c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"]
    attn = {"wq": ((n, d, h * hd), "normal"),
            "wk": ((n, d, kv * hd), "normal"),
            "wv": ((n, d, kv * hd), "normal"),
            "wo": ((n, h * hd, d), "normal")}
    if c.get("attention_bias", False):
        attn.update(bq=((n, h * hd), "zeros"), bk=((n, kv * hd), "zeros"),
                    bv=((n, kv * hd), "zeros"))
    if c.get("qk_norm", False):
        attn.update(q_norm=((n, hd), "ones"), k_norm=((n, hd), "ones"))
    layer = {"attn": attn,
             "mlp": {"w_gate": ((n, d, f), "normal"),
                     "w_up": ((n, d, f), "normal"),
                     "w_down": ((n, f, d), "normal")},
             "norm1": {"scale": ((n, d), "ones")},
             "norm2": {"scale": ((n, d), "ones")}}
    embed = {"tok": ((v, d), "normal")}
    if not c["tie_word_embeddings"]:
        embed["out"] = ((d, v), "normal")
    return {"embed": embed, "final_norm": {"scale": ((d,), "ones")},
            "segments": [{"pos0": layer}]}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


@partial(jax.jit, static_argnames=("c", "dtypes"))
def _draw(key, *, c, dtypes):
    c = dict(c)
    leaves, tree = jax.tree_util.tree_flatten(leaf_specs(c), is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    std = float(c["initializer_range"])
    out = []
    for (shape, init), k, dt in zip(leaves, keys, dtypes):
        if init == "zeros":
            w = jnp.zeros(shape, jnp.float32)
        elif init == "ones":
            w = jnp.ones(shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) * std
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
    """x: (B, S, H, D), positions 0..S-1, rotate-half form."""
    s, dim = x.shape[1], x.shape[-1]
    half = dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]      # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("c", "fp8"))
def logits(c, params, tokens, pick, *, fp8: bool = False):
    """(B, S) tokens -> (B, P, V) float32 logits of the full forward pass at
    the (B, P) positions ``pick``.  Rows may be right-padded: a causal
    model's logits at a position never see what follows it.  ``c`` is the
    model's configuration as a hashable tuple of items."""
    c = dict(c)
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    b, s = tokens.shape
    x = params["embed"]["tok"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        y = _rms(x, p["norm1"]["scale"], eps)
        q, k, v = _mm(y, a["wq"], fp8), _mm(y, a["wk"], fp8), \
            _mm(y, a["wv"], fp8)
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, kv, hd)
        v = v.reshape(b, s, kv, hd)
        if "q_norm" in a:
            q = _rms(q, a["q_norm"], eps)
            k = _rms(k, a["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            * hd ** -0.5
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
        x = x + _mm(o.reshape(b, s, h * hd), a["wo"], fp8)
        y = _rms(x, p["norm2"]["scale"], eps)
        g = _mm(y, m["w_gate"], fp8)
        u = _mm(y, m["w_up"], fp8)
        x = x + _mm(jax.nn.silu(g) * u, m["w_down"], fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["segments"][0]["pos0"])
    x = jnp.take_along_axis(x, pick[:, :, None], axis=1)
    x = _rms(x, params["final_norm"]["scale"], eps)
    out = params["embed"]["tok"].T if c["tie_word_embeddings"] \
        else params["embed"]["out"]
    return _mm(x, out, fp8)
