"""MoE dispatch: gather/scatter grouped-matmul vs. a naive per-token oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.layers import Ctx
from repro.models.moe import moe_forward, moe_specs
from repro.models.params import init_params


def _swiglu(x, wg, wu, wd):
    g = x @ wg
    return ((g / (1 + np.exp(-g))) * (x @ wu)) @ wd  # silu(g) * u


def _naive_moe(p, x, cfg):
    """Per-token loop oracle (no capacity, exact top-k mixture): softmax
    over the chosen logits, or (sigmoid scoring) the chosen sigmoid scores,
    chosen with the bias added, normalised and scaled; plus the shared
    experts."""
    e = cfg.moe
    b, s, d = x.shape
    xt = np.asarray(x.reshape(b * s, d), np.float32)
    router = np.asarray(p["router"], np.float32)
    wg = np.asarray(p["w_gate"], np.float32)
    wu = np.asarray(p["w_up"], np.float32)
    wd = np.asarray(p["w_down"], np.float32)
    logits = xt @ router
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        if e.scoring == "sigmoid":
            score = 1 / (1 + np.exp(-logits[t]))
            top = np.argsort(-(score + np.asarray(p["router_bias"])))[: e.top_k]
            w = score[top] / score[top].sum() * e.routed_scale
        else:
            top = np.argsort(-logits[t])[: e.top_k]
            w = np.exp(logits[t, top] - logits[t, top].max())
            w = w / w.sum()
        for wi, ei in zip(w, top):
            out[t] += wi * _swiglu(xt[t], wg[ei], wu[ei], wd[ei])
        if e.n_shared:
            sh = p["shared"]
            out[t] += _swiglu(xt[t], *(np.asarray(sh[k], np.float32)
                                       for k in ("w_gate", "w_up", "w_down")))
    return out.reshape(b, s, d)


@pytest.mark.parametrize("arch", ["moonshot-v1-16b-a3b", "mixtral-8x22b"])
def test_moe_matches_naive_oracle(arch):
    cfg = get_smoke_config(arch)  # capacity_factor=8 => no drops
    ctx = Ctx(cfg=cfg)
    p = init_params(moe_specs(cfg), jax.random.PRNGKey(0), dtype_override=jnp.float32)
    if "router_bias" in p:   # a selection bias that changes choices
        p["router_bias"] = 0.5 * jax.random.normal(jax.random.PRNGKey(2), p["router_bias"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), jnp.float32)
    # force fp32 compute for the comparison
    import dataclasses
    ctx32 = Ctx(cfg=dataclasses.replace(cfg, compute_dtype="float32"))
    y, aux, _ = moe_forward(ctx32, p, x)
    y_ref = _naive_moe(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=1e-4, rtol=1e-3)
    assert float(aux) > 0  # load-balance loss is positive


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor=1.0, dropped tokens produce zero expert output
    but the layer stays finite and shaped."""
    import dataclasses
    from repro.models.config import MoEConfig

    cfg = get_smoke_config("mixtral-8x22b")
    cfg = dataclasses.replace(
        cfg, moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                           capacity_factor=1.0, min_capacity=1)
    )
    ctx = Ctx(cfg=cfg)
    p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.bfloat16)
    y, _, _ = moe_forward(ctx, p, x)
    assert y.shape == x.shape
    assert jnp.isfinite(y.astype(jnp.float32)).all()


def test_int8_expert_quantization():
    """Serve-time int8 expert weights: numerics within int8 tolerance and
    spec tree carries int8 storage + scales."""
    import dataclasses
    from repro.models.moe import quantize_expert_params

    cfg = dataclasses.replace(
        get_smoke_config("mixtral-8x22b"), compute_dtype="float32",
        quant_experts_serve=True,
    )
    p32 = init_params(moe_specs(cfg), jax.random.PRNGKey(0), dtype_override=jnp.float32)
    pq = quantize_expert_params(p32)
    assert pq["w_gate"].dtype == jnp.int8
    ctx = Ctx(cfg=cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
    y32, _, _ = moe_forward(ctx, p32, x)
    yq, _, _ = moe_forward(ctx, pq, x)
    rel = float(jnp.max(jnp.abs(yq - y32))) / float(jnp.max(jnp.abs(y32)))
    assert rel < 0.05, rel
    # quantized serve specs carry int8 weights + scale leaves
    qspecs = moe_specs(cfg, quantized=True)
    assert qspecs["w_gate"].dtype == jnp.int8
    assert "w_gate_scale" in qspecs


def test_moe_grad_flows():
    cfg = get_smoke_config("moonshot-v1-16b-a3b")
    ctx = Ctx(cfg=cfg)
    p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))

    def loss(p_):
        x = jnp.ones((1, 8, cfg.d_model), jnp.bfloat16) * 0.1
        y, aux, _ = moe_forward(ctx, p_, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + 0.01 * aux

    grads = jax.grad(loss)(p)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0
