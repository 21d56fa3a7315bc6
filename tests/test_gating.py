"""Temporal gating unit (Eq. 5-6) invariants + meta-training curriculum."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.curriculum import CurriculumConfig, offline_warmup, online_finetune
from repro.core.features import feature_dim, motion_features, segment_features
from repro.core.gating import GateConfig, gate_loss, gate_scan, gate_specs, init_state
from repro.data.video import VideoConfig, generate_stream
from repro.models.params import init_params

GCFG = GateConfig(d_feature=8, d_hidden=16, var_window=4)


def _params(seed=0):
    return init_params(gate_specs(GCFG), jax.random.PRNGKey(seed))


def test_tau_in_unit_interval():
    p = _params()
    dxs = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    taus, gs, _ = gate_scan(GCFG, p, dxs)
    assert taus.shape == (32,)
    assert jnp.all((taus >= 0) & (taus <= 1))
    assert jnp.all((gs >= 0) & (gs <= 1))


def test_volatility_opens_gate():
    """Eq. 5: with alpha > 0, higher recent variance -> larger gate."""
    p = _params()
    p = dict(p, alpha=jnp.asarray(5.0))
    calm = jnp.zeros((16, 8))
    volatile = jax.random.normal(jax.random.PRNGKey(2), (16, 8)) * 2.0
    _, g_calm, _ = gate_scan(GCFG, p, calm)
    _, g_vol, _ = gate_scan(GCFG, p, volatile)
    assert float(g_vol[4:].mean()) > float(g_calm[4:].mean())


def test_state_streaming_consistency():
    """Scanning in two chunks with carried state == one scan."""
    p = _params()
    dxs = jax.random.normal(jax.random.PRNGKey(3), (20, 8))
    taus_full, _, _ = gate_scan(GCFG, p, dxs)
    t1, _, st = gate_scan(GCFG, p, dxs[:10])
    t2, _, _ = gate_scan(GCFG, p, dxs[10:], st)
    np.testing.assert_allclose(jnp.concatenate([t1, t2]), taus_full, atol=1e-6)


def test_offline_warmup_reduces_loss():
    rng = np.random.default_rng(0)

    def data():
        while True:
            dxs = rng.normal(0, 1, (8, 12, GCFG.d_feature)).astype(np.float32)
            # oracle: cloud benefit correlates with feature magnitude
            labels = (np.linalg.norm(dxs, axis=-1) > 3.2).astype(np.float32)
            yield jnp.asarray(dxs), jnp.asarray(labels)

    ccfg = CurriculumConfig(warmup_steps=60, lr=5e-2)
    params, losses = offline_warmup(GCFG, data(), ccfg, jax.random.PRNGKey(0))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]), "warm-up did not learn"


def test_online_proximal_stays_near_anchor():
    rng = np.random.default_rng(1)

    def data():
        while True:
            dxs = rng.normal(0, 1, (4, 8, GCFG.d_feature)).astype(np.float32)
            labels = np.ones((4, 8), np.float32)  # drifted objective
            yield jnp.asarray(dxs), jnp.asarray(labels)

    params = _params()
    ccfg = CurriculumConfig(online_steps=40, lr=5e-2, mu=10.0)
    tuned, _ = online_finetune(GCFG, params, data(), ccfg)
    drift = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree_util.tree_leaves(tuned), jax.tree_util.tree_leaves(params))
    )
    ccfg_free = CurriculumConfig(online_steps=40, lr=5e-2, mu=0.0)
    free, _ = online_finetune(GCFG, params, data(), ccfg_free)
    drift_free = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree_util.tree_leaves(free), jax.tree_util.tree_leaves(params))
    )
    assert drift < drift_free, "proximal term did not constrain drift"


def test_motion_features_shapes_and_ma():
    frames = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (17, 32, 32)), jnp.float32)
    dx = motion_features(frames)
    assert dx.shape == (16, feature_dim())
    seg = segment_features(frames, 4)
    assert seg.shape == (4, feature_dim())


def test_motion_features_track_motion_level():
    """Faster blob motion -> larger mean |diff| feature (the 'stats' block)."""
    vcfg = VideoConfig(height=48, width=48)
    slow, _ = generate_stream(vcfg, 4, motion_profile=np.full(4, 0.05),
                              rng=np.random.default_rng(0))
    fast, _ = generate_stream(vcfg, 4, motion_profile=np.full(4, 0.95),
                              rng=np.random.default_rng(0))
    f_slow = motion_features(jnp.asarray(slow))[:, -3]   # mean-diff stat
    f_fast = motion_features(jnp.asarray(fast))[:, -3]
    assert float(f_fast.mean()) > float(f_slow.mean())


def test_resync_cadence_one_matches_looped_oracle():
    """``resync_period=1`` recomputes the running Σ/Σ² from the exact ring
    buffer every step, so the batched incremental volatility is drift-free:
    the running sums equal a fresh buffer scan bitwise at every step, and the
    taus match the looped per-stream ``gate_step`` oracle."""
    from repro.core.gating import gate_step, gate_step_batch, init_batch_state

    cfg = GateConfig(d_feature=8, d_hidden=16, var_window=4, resync_period=1)
    p = init_params(gate_specs(cfg), jax.random.PRNGKey(4))
    steps, m = 9, 3
    dxs = jax.random.normal(jax.random.PRNGKey(5), (steps, m, cfg.d_feature))

    states = [init_state(cfg) for _ in range(m)]
    st = init_batch_state(cfg, m)
    # the fresh reduction is compiled like the in-step resync: XLA contracts
    # the Σx² square-and-add into FMAs there, which eager op-by-op
    # evaluation does not
    exact = jax.jit(lambda b: (b.sum(axis=1), jnp.square(b).sum(axis=1)))
    for t in range(steps):
        st, (tau, _) = gate_step_batch(cfg, p, st, dxs[t])
        # every step: the incremental sums ARE the exact buffer reduction
        want_sum, want_sumsq = exact(st.var_buf)
        np.testing.assert_array_equal(np.asarray(st.var_sum),
                                      np.asarray(want_sum))
        np.testing.assert_array_equal(np.asarray(st.var_sumsq),
                                      np.asarray(want_sumsq))
        for i in range(m):
            states[i], (tau_ref, _) = gate_step(cfg, p, states[i], dxs[t, i])
            np.testing.assert_allclose(
                float(tau[i]), float(tau_ref), atol=1e-5)
