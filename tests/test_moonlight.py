"""Moonlight-16B-A3B (latent attention, sigmoid-routed shared-expert MoE)
against its plain reference, ``bench/ref/moonlight_ref.py``, at a small
width on seeded random weights, computing in float32 so that a tolerance
only has to cover float32 rounding between two orders of summation (the
reference at ``Precision.HIGHEST``).  Logits and hidden values here are of
order 1, so 1e-4 absolute is a few hundred float32 ulps: enough for
reordered sums over widths <= 96, far below any fault (a dropped slot, a
wrong expert or weight moves them by 1e-2 or more)."""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import Ctx, decode_step, model_specs, prefill
from repro.models.config import MLAConfig, ModelConfig, MoEConfig
from repro.models.mla import mla_forward, mla_specs
from repro.models.moe import _capacity_dispatch, moe_forward, route
from repro.models.params import init_params, shape_dtypes
from repro.serving.pools import ModelPool

_REF = pathlib.Path(__file__).resolve().parents[1] / "bench" / "ref" / "moonlight_ref.py"
_spec = importlib.util.spec_from_file_location("moonlight_ref", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

TOL = 1e-4
E, HELD = 16, 4


def _cfg(n_held=HELD, offset=4, **kw) -> ModelConfig:
    return ModelConfig(
        name="moonlight-small", family="moe", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, head_dim=24, d_ff=96, vocab_size=256,
        rope_theta=50_000.0, norm_eps=1e-5, layer_pattern=("mla",),
        first_k_dense=1,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                      v_head_dim=16),
        moe=MoEConfig(num_experts=E, top_k=6, d_expert=32, scoring="sigmoid",
                      routed_scale=2.446, n_shared=2, n_held=n_held,
                      expert_offset=offset),
        compute_dtype="float32", attn_chunk=16, **kw)


def _rc(cfg: ModelConfig) -> tuple:
    m, e = cfg.mla, cfg.moe
    return tuple(dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.num_heads,
        kv_lora_rank=m.kv_lora_rank, qk_nope_head_dim=m.qk_nope_head_dim,
        qk_rope_head_dim=m.qk_rope_head_dim, v_head_dim=m.v_head_dim,
        intermediate_size=cfg.d_ff, moe_intermediate_size=e.d_expert,
        n_routed_experts=e.held, router_experts=e.num_experts,
        expert_offset=e.expert_offset, n_shared_experts=e.n_shared,
        num_experts_per_tok=e.top_k, routed_scaling_factor=e.routed_scale,
        first_k_dense_replace=cfg.first_k_dense,
        num_hidden_layers=cfg.num_layers, vocab_size=cfg.vocab_size,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        initializer_range=0.1, score_bias_std=0.05).items())


def _weights(cfg, seed=0):
    return ref.make_weights(_rc(cfg), jax.random.PRNGKey(seed))


def _tokens(b, s, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, 256)


def test_parameter_tree_is_the_references():
    cfg = _cfg()

    got = shape_dtypes(model_specs(cfg))
    want = ref.weight_shapes(_rc(cfg))
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape, got, want))
    assert sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(want)) \
        == cfg.param_count()


def test_prefill_logits_match_reference():
    cfg = _cfg()
    params = _weights(cfg)
    toks = _tokens(3, 20)
    got, _ = jax.jit(lambda p, t: prefill(Ctx(cfg=cfg), p, {"tokens": t}))(params, toks)
    want = ref.logits(_rc(cfg), params, toks, jnp.full((3, 1), 19, jnp.int32))[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_slab_decode_steps_through_latent_cache_match_full_forward():
    """Prompts of two lengths prefilled into slots of a 6-slot slab; each
    decode step's logits, per slot at its own depth, against the
    reference's full forward over the prompt and the tokens fed so far."""
    cfg = _cfg()
    params = _weights(cfg)
    pool = ModelPool(cfg, jax.random.PRNGKey(0), n_slots=6)
    pool.params = params
    slab = pool.make_slab(6, 24)
    assert slab["segments"][1]["pos0"]["ckv"].shape == (2, 6, 24 + 64, 32)
    seqs = {}
    for slots, n, seed in (([4, 1], 12, 2), ([0], 20, 3)):
        toks = _tokens(len(slots), n, seed)
        ids, cache = pool.prefill_batch(toks)
        slab = pool.insert_slab(slab, cache, slots)
        for i, s in enumerate(slots):
            seqs[s] = list(np.asarray(toks[i])) + [int(ids[i])]
    step = jax.jit(lambda p, c, t: decode_step(pool.ctx, p, c, {"tokens": t}))
    last = np.zeros((6,), np.int32)
    for s in seqs:
        last[s] = seqs[s][-1]
    got = {s: [] for s in seqs}
    for _ in range(4):
        logits, slab = step(params, slab, jnp.asarray(last)[:, None])
        for s, seq in seqs.items():
            got[s].append(np.asarray(logits[s]))
            seq.append(int(jnp.argmax(logits[s])))
            last[s] = seq[-1]
    # one reference forward per slot's whole sequence (right-padded to one
    # width), read at the position of each decode step's input token
    order = sorted(seqs)
    width = max(len(seqs[s]) for s in order)
    toks = np.zeros((len(order), width), np.int32)
    pick = np.zeros((len(order), 4), np.int32)
    for i, s in enumerate(order):
        toks[i, :len(seqs[s])] = seqs[s]
        pick[i] = len(seqs[s]) - 5 + np.arange(4)
    want = ref.logits(_rc(cfg), params, jnp.asarray(toks), jnp.asarray(pick))
    for i, s in enumerate(order):
        np.testing.assert_allclose(np.stack(got[s]), np.asarray(want[i]), atol=TOL)
    assert ModelPool.counters(slab)["expert_rows"] > 0


def test_absorbed_decode_matches_expanded_attention():
    cfg = _cfg()
    p = init_params(mla_specs(cfg), jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    expanded = jax.jit(lambda p, x, pos: mla_forward(
        Ctx(cfg=cfg, mode="prefill"), p, x, positions=pos, cache_out_len=12))
    full, _ = expanded(p, x, pos)
    _, cache = expanded(p, x[:, :8], pos[:, :8])
    cache["length"] = jnp.asarray([8, 8], jnp.int32)
    last, _ = jax.jit(lambda p, x, pos, c: mla_forward(
        Ctx(cfg=cfg, mode="decode"), p, x, positions=pos, cache=c))(
        p, x[:, 8:], pos[:, 8:], cache)
    np.testing.assert_allclose(np.asarray(last[:, 0]), np.asarray(full[:, 8]),
                               atol=1e-5)


def _route_inputs(seed):
    cfg = _cfg()
    params = _weights(cfg, seed)
    mlp = jax.tree_util.tree_map(lambda w: w[0], params["segments"][1]["pos0"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(seed + 100), (64, cfg.d_model))
    return cfg, mlp, x


def test_bias_enters_selection_and_not_the_weights():
    cfg, mlp, x = _route_inputs(7)
    w, ids, logits = route(cfg, mlp, x, jnp.float32)
    _, unbiased = route(cfg, dict(mlp, router_bias=jnp.zeros(E)), x, jnp.float32)[:2]
    # this seed's bias flips some choices
    assert np.any(np.sort(np.asarray(ids), -1) != np.sort(np.asarray(unbiased), -1))
    scores = jax.nn.sigmoid(x @ mlp["router"])
    assert np.array_equal(np.asarray(ids), np.asarray(
        jax.lax.top_k(scores + mlp["router_bias"], 6)[1]))
    chosen = jnp.take_along_axis(scores, ids, -1)
    np.testing.assert_allclose(np.asarray(w), np.asarray(
        chosen / chosen.sum(-1, keepdims=True) * 2.446), rtol=1e-6)


def test_weights_are_normalised_over_the_chosen_and_scaled():
    cfg, mlp, x = _route_inputs(8)
    w, _, _ = route(cfg, mlp, x, jnp.float32)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.446, rtol=1e-6)


def test_serving_is_dropless_under_uneven_routing():
    """A bias that sends every token to the same six experts overflows the
    training path's capacity (it drops); the serving path computes every
    slot and matches the reference."""
    cfg, mlp, x = _route_inputs(9)
    mlp = dict(mlp, router_bias=jnp.zeros(E).at[4:10].set(5.0))
    xb = x[None]
    want = ref.experts(dict(_rc(cfg)), mlp, x)
    got, _, counts = jax.jit(lambda m, x: moe_forward(
        Ctx(cfg=cfg, mode="prefill"), m, x))(mlp, xb)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=TOL)
    # held experts 4..7 take 4 of the 6 forced choices of all 64 tokens
    assert counts.tolist() == [64 * 4, 4]
    tight = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=1.0, min_capacity=1))
    w, ids, _ = route(tight, mlp, x, jnp.float32)
    dropped = jax.jit(lambda m, x, w, ids: _capacity_dispatch(
        Ctx(cfg=tight), m, x, w, ids))(mlp, x, w, ids)
    shared = ref._swiglu(x, mlp["shared"], False)
    assert float(jnp.max(jnp.abs(dropped + shared - want))) > 1e-2


def test_held_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of the experts, the shared experts (which every
    chip computes alike) counted once, add up to the uncut reference."""
    uncut = _cfg(n_held=E, offset=0)
    _, mlp, x = _route_inputs(10)
    full = ref.make_weights(_rc(uncut), jax.random.PRNGKey(11))
    mlp = jax.tree_util.tree_map(lambda w: w[0], full["segments"][1]["pos0"]["mlp"])
    want = ref.experts(dict(_rc(uncut)), mlp, x)
    shared = ref._swiglu(x, mlp["shared"], False)
    total = -3 * shared
    for i in range(4):
        cfg = _cfg(n_held=E // 4, offset=i * E // 4)
        part = dict(mlp, **{k: mlp[k][i * 4:(i + 1) * 4]
                            for k in ("w_gate", "w_up", "w_down")})
        total = total + jax.jit(lambda m, x: moe_forward(
            Ctx(cfg=cfg, mode="decode"), m, x)[0])(part, x[None])[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=TOL)
