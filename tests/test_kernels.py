"""Per-kernel validation: shape/dtype sweeps, interpret mode vs pure-jnp
oracle (assert_allclose)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kv,s,d,win",
    [
        (2, 4, 2, 256, 64, None),
        (1, 8, 1, 128, 32, None),   # MQA
        (2, 4, 4, 256, 64, 64),     # MHA + window
        (1, 2, 2, 128, 128, 32),
        (1, 16, 4, 512, 64, 128),
    ],
)
def test_flash_attention(b, h, kv, s, d, win, dtype):
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, window=win, block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, window=win)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,s,d", [(2, 8, 2, 512, 64), (1, 4, 1, 256, 128), (3, 6, 6, 512, 32)])
def test_decode_attention(b, h, kv, s, d, dtype):
    from repro.kernels.decode_attention.kernel import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    vc = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    length = jnp.asarray([s // 2, s // 4, s][:b], jnp.int32)
    out = decode_attention(q, kc, vc, length, block_s=128, interpret=True)
    ref = decode_attention_ref(q, kc, vc, length)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("b,s,di,n,bt,bd", [(2, 256, 128, 16, 64, 64), (1, 128, 64, 8, 128, 64), (2, 512, 256, 16, 64, 128)])
def test_mamba_scan(b, s, di, n, bt, bd):
    from repro.kernels.mamba_scan.kernel import selective_scan
    from repro.kernels.mamba_scan.ref import selective_scan_ref

    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) * 0.5)
    B = jax.random.normal(ks[2], (b, s, n))
    C = jax.random.normal(ks[3], (b, s, n))
    A = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.2)
    D = jnp.ones((di,))
    y, h = selective_scan(x, dt, B, C, A, D, block_t=bt, block_d=bd, interpret=True)
    yr, hr = selective_scan_ref(x, dt, B, C, A, D)
    np.testing.assert_allclose(y, yr, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(h, hr, atol=5e-5, rtol=5e-5)


def test_mamba_scan_carries_state():
    """Scanning two halves with carried state == one full scan."""
    from repro.kernels.mamba_scan.kernel import selective_scan

    b, s, di, n = 1, 256, 64, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) * 0.5)
    B = jax.random.normal(ks[2], (b, s, n))
    C = jax.random.normal(ks[3], (b, s, n))
    A = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.2)
    D = jnp.ones((di,))
    y_full, h_full = selective_scan(x, dt, B, C, A, D, block_t=64, block_d=64, interpret=True)
    half = s // 2
    y1, h1 = selective_scan(x[:, :half], dt[:, :half], B[:, :half], C[:, :half], A, D,
                            block_t=64, block_d=64, interpret=True)
    y2, h2 = selective_scan(x[:, half:], dt[:, half:], B[:, half:], C[:, half:], A, D,
                            h0=h1, block_t=64, block_d=64, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(h2, h_full, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("b,s,w,bt,bw", [(2, 256, 128, 64, 64), (1, 128, 256, 128, 128), (2, 512, 64, 64, 64)])
def test_rglru_scan(b, s, w, bt, bw):
    from repro.kernels.rglru.kernel import rglru_scan
    from repro.kernels.rglru.ref import rglru_scan_ref

    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (b, s, w))
    r = jax.nn.sigmoid(jax.random.normal(ks[1], (b, s, w)))
    i = jax.nn.sigmoid(jax.random.normal(ks[2], (b, s, w)))
    la = -jax.nn.softplus(jax.random.normal(ks[3], (w,)))
    y, h = rglru_scan(x, r, i, la, block_t=bt, block_w=bw, interpret=True)
    yr, hr = rglru_scan_ref(x, r, i, la)
    np.testing.assert_allclose(y, yr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(h, hr, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,d,m,bb", [(64, 35, 32, 32), (128, 16, 64, 64), (32, 8, 8, 32)])
def test_temporal_gate_cell(b, d, m, bb):
    from repro.core.gating import GateConfig, gate_specs
    from repro.kernels.temporal_gate.kernel import gate_cell
    from repro.kernels.temporal_gate.ref import gate_cell_ref
    from repro.models.params import init_params

    gcfg = GateConfig(d_feature=d, d_hidden=m)
    p = init_params(gate_specs(gcfg), jax.random.PRNGKey(3))
    dx = jax.random.normal(KEY, (b, d))
    h = jax.random.normal(KEY, (b, m)) * 0.1
    vol = jax.random.uniform(KEY, (b,))
    hn, tau, gm = gate_cell(dx, h, vol[:, None], p, block_b=bb, interpret=True)
    hr, taur, gmr = gate_cell_ref(dx, h, vol, p)
    np.testing.assert_allclose(hn, hr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(tau[:, 0], taur, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(gm[:, 0], gmr, atol=1e-5, rtol=1e-5)


try:
    from hypothesis import given, settings
    import hypothesis.strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # optional dep: skip only the property-based test
    HAS_HYPOTHESIS = False

if not HAS_HYPOTHESIS:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_flash_attention_property():
        pass

else:

    @settings(max_examples=10, deadline=None)
    @given(
        b=st.integers(1, 3),
        kv=st.sampled_from([1, 2, 4]),
        g=st.sampled_from([1, 2, 4]),
        nq=st.integers(1, 4),
        d=st.sampled_from([32, 64]),
        windowed=st.booleans(),
    )
    def test_flash_attention_property(b, kv, g, nq, d, windowed):
        """Random GQA/window geometries: kernel == oracle (property-based)."""
        from repro.kernels.flash_attention.kernel import flash_attention
        from repro.kernels.flash_attention.ref import attention_ref

        h = kv * g
        s = 64 * nq
        win = 32 if windowed else None
        ks = jax.random.split(jax.random.PRNGKey(b * 100 + h * 10 + nq), 3)
        q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, kv, s, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, kv, s, d), jnp.float32)
        out = flash_attention(q, k, v, window=win, block_q=64, block_k=64, interpret=True)
        ref = attention_ref(q, k, v, window=win)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_gate_kernel_matches_model_cell():
    """The fused kernel must agree with the model-level gate_step (Eq. 5-6)."""
    from repro.core.gating import GateConfig, GateState, gate_specs, gate_step
    from repro.kernels.temporal_gate.ref import gate_cell_ref
    from repro.models.params import init_params

    gcfg = GateConfig(d_feature=12, d_hidden=16, var_window=4)
    p = init_params(gate_specs(gcfg), jax.random.PRNGKey(5))
    dx = jax.random.normal(KEY, (12,))
    st = GateState(
        h=jax.random.normal(KEY, (16,)) * 0.1,
        var_buf=jax.random.normal(KEY, (4, 12)) * 0.2,
        var_idx=jnp.asarray(2, jnp.int32),
    )
    new_state, (tau, gmean) = gate_step(gcfg, p, st, dx)
    # replicate volatility used by gate_step
    buf = jax.lax.dynamic_update_slice_in_dim(st.var_buf, dx[None], 2, axis=0)
    vol = jnp.var(buf, axis=0).mean()
    h_ref, tau_ref, g_ref = gate_cell_ref(dx[None], st.h[None], vol[None], p)
    np.testing.assert_allclose(new_state.h, h_ref[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(tau, tau_ref[0], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("m,p,f,bm,bf", [
    (16, 16, 50, 8, 32),    # M and F both padded (50 % 32 != 0)
    (13, 16, 50, 8, 16),    # odd M: padding path
    (8, 1, 50, 8, 64),      # P=1 degenerate pole set, F < block
    (64, 16, 128, 32, 64),  # exact tiling, multi-tile argmin hand-off
])
def test_ccg_master(m, p, f, bm, bf):
    """Pallas masked CCG master step (interpret) == jnp oracle, including the
    empty-scenario-set (η=0) and all-infeasible (obj=BIG) lanes and argmin
    ties across F tiles."""
    from repro.kernels.ccg_master.kernel import ccg_master as ccg_master_pallas
    from repro.kernels.ccg_master.ops import ccg_master
    from repro.kernels.ccg_master.ref import ccg_master_ref

    ks = jax.random.split(KEY, 4)
    rec = jax.random.uniform(ks[0], (m, p, f), jnp.float32, 0.0, 5.0)
    scen = (jax.random.uniform(ks[1], (m, p)) > 0.5).astype(jnp.float32)
    scen = scen.at[0].set(0.0)                    # empty scenario set lane
    fs_ok = jax.random.uniform(ks[2], (m, f)) > 0.3
    fs_ok = fs_ok.at[1].set(False)                # all-infeasible lane
    c1 = jax.random.uniform(ks[3], (f,), jnp.float32, 0.0, 1.0)

    y_ref, od_ref = ccg_master_ref(rec, scen, fs_ok, c1)
    y, od = ccg_master(rec, scen, fs_ok, c1, block_m=bm, block_f=bf,
                       force="interpret")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(np.asarray(od), np.asarray(od_ref))

    # tie-breaking: duplicate the minimum across tiles -> lowest index wins
    rec_t = jnp.zeros((4, p, f))
    c1_t = jnp.zeros((f,)).at[jnp.asarray([3, f - 2])].set(-1.0)
    y_t, _ = ccg_master(rec_t, jnp.zeros((4, p)), jnp.ones((4, f), bool), c1_t,
                        block_m=bm, block_f=bf, force="interpret")
    assert np.all(np.asarray(y_t) == 3)

    # direct kernel call on exact tiles (no ops padding) as well
    if m % bm == 0 and f % bf == 0:
        y_k, od_k = ccg_master_pallas(
            rec, scen, fs_ok.astype(jnp.float32), c1[None, :],
            block_m=bm, block_f=bf, interpret=True)
        np.testing.assert_array_equal(np.asarray(y_k)[:, 0], np.asarray(y_ref))
        np.testing.assert_array_equal(np.asarray(od_k)[:, 0],
                                      np.asarray(od_ref))


@pytest.mark.parametrize("m,bm,gamma", [
    (16, 8, 2),     # exact tiling
    (13, 8, 2),     # odd M: ops padding path
    (7, 128, 2),    # whole batch smaller than one block
    (9, 8, 0),      # Γ=0 degenerate pole set (P=1)
])
def test_ccg_encode(m, bm, gamma):
    """Fused table-free task encoding (jnp ref + Pallas interpret) ==
    the table-based ``_encode_tasks`` oracle, bit for bit: feasibility
    bitmask, recourse slab, and the flat accuracy argmax — including an
    all-infeasible lane (fallback path) and an everything-feasible lane."""
    from repro.core.cost_model import SystemConfig
    from repro.core.robust import RobustProblem, _encode_tasks
    from repro.kernels.ccg_encode.ops import ccg_encode
    from repro.kernels.ccg_encode.ref import ccg_encode_ref

    sys_ = SystemConfig(gamma=gamma)
    prob = RobustProblem.build(sys_)
    lat = prob.lat
    rng = np.random.default_rng(m * 10 + gamma)
    z = rng.uniform(0, 1, m)
    aq = rng.uniform(0.5, 0.75, m)
    aq[0] = 0.99    # all-infeasible lane: margin-relaxation fallback
    aq[1] = 0.0     # everything-feasible lane: full bitmask
    z = jnp.asarray(z, jnp.float32)
    aq = jnp.asarray(aq, jnp.float32)

    # table-based oracle
    f_flat, feas_f, fs_ok, rec_tab = _encode_tasks(prob, z, aq)
    pow2 = 2 ** jnp.arange(sys_.num_versions)
    code_tab = np.asarray((feas_f * pow2[None, None]).sum(axis=-1))
    best_tab = np.asarray(f_flat.reshape(m, -1).argmax(axis=1))
    assert not np.asarray(fs_ok)[0].any() and np.asarray(fs_ok)[1].all()

    args = (z, aq, lat.rn_flat, lat.pn_flat, lat.tier_flat,
            prob.b2_scaled, prob.rec_table)
    kw = dict(margin=sys_.acc_margin_robust, num_versions=sys_.num_versions)
    for force, blk in (("ref", 128), ("interpret", bm)):
        code, rec, best = ccg_encode(*args, block_m=blk, force=force, **kw)
        np.testing.assert_array_equal(np.asarray(code), code_tab, err_msg=force)
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(rec_tab),
                                      err_msg=force)
        np.testing.assert_array_equal(np.asarray(best), best_tab, err_msg=force)

    # the raw ref entry point agrees too (no dispatch wrapper)
    code_r, rec_r, best_r = ccg_encode_ref(
        z, aq, lat.rn_flat, lat.pn_flat, lat.tier_flat, prob.rec_table,
        sys_.acc_margin_robust, sys_.num_versions)
    np.testing.assert_array_equal(np.asarray(code_r), code_tab)
    np.testing.assert_array_equal(np.asarray(rec_r), np.asarray(rec_tab))
    np.testing.assert_array_equal(np.asarray(best_r), best_tab)


def test_ccg_encode_argmax_tie_breaking():
    """The running flat argmax must break accuracy ties exactly like
    ``argmax`` over the (F·K) flat space: saturated (clipped-to-1) surfaces
    tie across many configs -> lowest flat index wins."""
    from repro.core.cost_model import SystemConfig
    from repro.core.robust import RobustProblem, _encode_tasks
    from repro.kernels.ccg_encode.ops import ccg_encode

    # huge version ladder ceiling saturates accuracy at the clip for many
    # (r, p, k, tier) combos -> widespread exact ties at 1.0... the formula
    # caps a_max below 1, so instead drive z=0: accuracy is then independent
    # of p, guaranteeing Z-way exact ties at every (r, k, tier)
    sys_ = SystemConfig()
    prob = RobustProblem.build(sys_)
    lat = prob.lat
    m = 6
    z = jnp.zeros((m,), jnp.float32)
    aq = jnp.full((m,), 0.7, jnp.float32)
    f_flat, *_ = _encode_tasks(prob, z, aq)
    best_tab = np.asarray(f_flat.reshape(m, -1).argmax(axis=1))
    for force in ("ref", "interpret"):
        _, _, best = ccg_encode(
            z, aq, lat.rn_flat, lat.pn_flat, lat.tier_flat,
            prob.b2_scaled, prob.rec_table, block_m=8, force=force,
            margin=sys_.acc_margin_robust, num_versions=sys_.num_versions)
        np.testing.assert_array_equal(np.asarray(best), best_tab, err_msg=force)


_SOLVE_KEYS = ("route", "r", "p", "v", "o_up", "o_down", "iters", "infeasible")


@pytest.mark.parametrize("m,gamma,warm", [
    (16, 2, None),       # cold solve, exact tiling
    (13, 2, "mixed"),    # odd M: ops padding path; warm starts with -1 misses
    (9, 0, "hit"),       # Γ=0 degenerate pole set (P=1)
    (256, 2, "mixed"),   # live-lane compaction tail in the jnp ref
])
def test_ccg_solve(m, gamma, warm):
    """Fully fused CCG solver (jnp ref + Pallas interpret) == both retained
    oracles — the unrolled masked ``solve_ccg`` and the early-exit
    ``solve_ccg_while`` — bit for bit on every output: decisions, bounds,
    iteration counts, and the infeasibility flag.  Covers warm-start misses
    (-1 lanes), an all-infeasible lane, the Γ=0 single-pole degenerate set,
    and the M≥256 live-lane-compaction tail."""
    from repro.core.cost_model import SystemConfig
    from repro.core.robust import (RobustProblem, solve_ccg, solve_ccg_fused,
                                   solve_ccg_while)

    sys_ = SystemConfig(gamma=gamma)
    prob = RobustProblem.build(sys_)
    rng = np.random.default_rng(m * 10 + gamma)
    z = rng.uniform(0, 1, m)
    aq = rng.uniform(0.5, 0.75, m)
    aq[0] = 0.99    # all-infeasible lane: fallback config path
    z = jnp.asarray(z, jnp.float32)
    aq = jnp.asarray(aq, jnp.float32)
    wy = None
    if warm == "mixed":
        wy = jnp.asarray(rng.integers(-1, prob.lat.n_flat, m), jnp.int32)
    elif warm == "hit":
        wy = jnp.asarray(rng.integers(0, prob.lat.n_flat, m), jnp.int32)

    unrolled = solve_ccg(prob, z, aq, warm_y=wy)
    early = solve_ccg_while(prob, z, aq, warm_y=wy)
    for force in ("ref", "interpret"):
        fused = solve_ccg_fused(prob, z, aq, warm_y=wy, force=force)
        for k in _SOLVE_KEYS:
            np.testing.assert_array_equal(
                np.asarray(fused[k]), np.asarray(unrolled[k]),
                err_msg=f"{force}:{k} vs solve_ccg")
            np.testing.assert_array_equal(
                np.asarray(fused[k]), np.asarray(early[k]),
                err_msg=f"{force}:{k} vs solve_ccg_while")


def test_ccg_solve_argmin_tie_breaking():
    """z=0 makes accuracy independent of fps -> widespread exact objective
    ties in the master argmin; the fused solver must break them at the
    lowest flat index exactly like the oracles."""
    from repro.core.cost_model import SystemConfig
    from repro.core.robust import RobustProblem, solve_ccg, solve_ccg_fused

    prob = RobustProblem.build(SystemConfig())
    m = 6
    z = jnp.zeros((m,), jnp.float32)
    aq = jnp.full((m,), 0.7, jnp.float32)
    oracle = solve_ccg(prob, z, aq)
    for force in ("ref", "interpret"):
        fused = solve_ccg_fused(prob, z, aq, force=force)
        for k in _SOLVE_KEYS:
            np.testing.assert_array_equal(
                np.asarray(fused[k]), np.asarray(oracle[k]),
                err_msg=f"{force}:{k}")


@pytest.mark.parametrize("dead_tier", [0, 1])
def test_ccg_encode_masked_tier(dead_tier):
    """Scenario outage lowered to the (F,) ``y_ok`` mask: every option on
    the dead tier must drop out of the feasibility bitmask AND out of the
    all-infeasible fallback argmax — on the jnp ref and the Pallas
    interpret path, bit-identically to the table-based oracle with the
    same ``tier_ok``."""
    from repro.core.cost_model import SystemConfig
    from repro.core.robust import RobustProblem, _encode_tasks
    from repro.kernels.ccg_encode.ops import ccg_encode

    sys_ = SystemConfig()
    prob = RobustProblem.build(sys_)
    lat = prob.lat
    m = 13          # odd M also exercises the Pallas padding path
    rng = np.random.default_rng(77 + dead_tier)
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = np.asarray(rng.uniform(0.5, 0.75, m), np.float32)
    aq[0] = 0.99    # all-infeasible lane: the fallback must survive masking
    aq = jnp.asarray(aq)

    tier_ok = np.ones(2, np.float32)
    tier_ok[dead_tier] = 0.0
    tier_ok = jnp.asarray(tier_ok)
    y_ok = lat.tier_y_ok(tier_ok)

    f_flat, feas_f, _, rec_tab = _encode_tasks(prob, z, aq, tier_ok=tier_ok)
    pow2 = 2 ** jnp.arange(sys_.num_versions)
    code_tab = np.asarray((feas_f * pow2[None, None]).sum(axis=-1))
    best_tab = np.asarray(f_flat.reshape(m, -1).argmax(axis=1))

    dead_cols = np.asarray(lat.tier_flat) == dead_tier
    tier_of_best = np.asarray(lat.tier_flat)[best_tab // sys_.num_versions]
    assert (code_tab[:, dead_cols] == 0).all()
    assert (tier_of_best == 1 - dead_tier).all()

    args = (z, aq, lat.rn_flat, lat.pn_flat, lat.tier_flat,
            prob.b2_scaled, prob.rec_table)
    kw = dict(margin=sys_.acc_margin_robust, num_versions=sys_.num_versions)
    for force in ("ref", "interpret"):
        code, rec, best = ccg_encode(*args, block_m=8, force=force,
                                     y_ok=y_ok, **kw)
        np.testing.assert_array_equal(np.asarray(code), code_tab, err_msg=force)
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(rec_tab),
                                      err_msg=force)
        np.testing.assert_array_equal(np.asarray(best), best_tab, err_msg=force)


@pytest.mark.parametrize("dead_tier", [0, 1])
def test_ccg_solve_masked_tier(dead_tier):
    """Fused solve under a whole-tier outage == both retained oracles with
    the same ``tier_ok``, and no decision — including the all-infeasible
    fallback lane — ever lands on the dead tier."""
    from repro.core.cost_model import SystemConfig
    from repro.core.robust import (RobustProblem, solve_ccg, solve_ccg_fused,
                                   solve_ccg_while)

    prob = RobustProblem.build(SystemConfig())
    m = 13
    rng = np.random.default_rng(88 + dead_tier)
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = np.asarray(rng.uniform(0.5, 0.75, m), np.float32)
    aq[0] = 0.99    # all-infeasible lane: fallback must pick a survivor
    aq = jnp.asarray(aq)
    tier_ok = jnp.zeros(2, jnp.float32).at[1 - dead_tier].set(1.0)

    unrolled = solve_ccg(prob, z, aq, tier_ok=tier_ok)
    early = solve_ccg_while(prob, z, aq, tier_ok=tier_ok)
    assert (np.asarray(unrolled["route"]) == 1 - dead_tier).all()
    for force in ("ref", "interpret"):
        fused = solve_ccg_fused(prob, z, aq, force=force, tier_ok=tier_ok)
        assert (np.asarray(fused["route"]) == 1 - dead_tier).all(), force
        for k in _SOLVE_KEYS:
            np.testing.assert_array_equal(
                np.asarray(fused[k]), np.asarray(unrolled[k]),
                err_msg=f"{force}:{k} vs solve_ccg")
            np.testing.assert_array_equal(
                np.asarray(fused[k]), np.asarray(early[k]),
                err_msg=f"{force}:{k} vs solve_ccg_while")


@pytest.mark.parametrize("m,bm", [
    (16, 8),     # exact tiling
    (13, 8),     # odd M: ops padding path
    (7, 256),    # whole batch smaller than one block
])
def test_c6_tail(m, bm):
    """Fused C6 repair tail (jnp ref + Pallas interpret) == the inline
    ``take_along_axis`` + ``accuracy_at`` round body, bit for bit: draw,
    reclaimable gain (including -BIG infeasible-demotion lanes), and the
    fps-vs-resolution demotion choice."""
    from repro.core.cost_model import SystemConfig, accuracy_at, fps_norm, res_norm
    from repro.core.lattice import DecisionLattice
    from repro.core.robust import BIG
    from repro.kernels.c6_tail.ops import c6_tail

    sys_ = SystemConfig()
    lat = DecisionLattice.build(sys_)
    rng = np.random.default_rng(m)
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.75, m), jnp.float32)
    r = jnp.asarray(rng.integers(0, sys_.n_res, m), jnp.int32)
    p = jnp.asarray(rng.integers(0, sys_.n_fps, m), jnp.int32)
    v = jnp.asarray(rng.integers(0, sys_.num_versions, m), jnp.int32)
    route = jnp.asarray(rng.integers(0, 2, m), jnp.int32)
    r = r.at[0].set(0)   # p floor lane
    p = p.at[0].set(0)   # ... gain must fall through to -BIG
    acc_thr = aq + sys_.acc_margin_robust

    panel = jnp.moveaxis(lat.bw, -1, 0)[route].reshape(m, -1)
    take = lambda ri, pi: jnp.take_along_axis(
        panel, (ri * sys_.n_fps + pi)[:, None], axis=1)[:, 0]
    bw_o = take(r, p)
    p_dn = jnp.maximum(p - 1, 0)
    r_dn = jnp.maximum(r - 1, 0)
    can_p_o = (p > 0) & (accuracy_at(sys_, z, r, p_dn, v, route) >= acc_thr)
    can_r_o = (r > 0) & (accuracy_at(sys_, z, r_dn, p, v, route) >= acc_thr)
    gain_o = jnp.where(can_p_o, bw_o - take(r, p_dn),
                       jnp.where(can_r_o, bw_o - take(r_dn, p), -BIG))

    for force in ("ref", "interpret"):
        bw, gain, can_p = c6_tail(
            panel, r, p, v, route, z, acc_thr, res_norm(sys_), fps_norm(sys_),
            n_fps=sys_.n_fps, block_m=bm, force=force)
        np.testing.assert_array_equal(np.asarray(bw), np.asarray(bw_o),
                                      err_msg=force)
        np.testing.assert_array_equal(np.asarray(gain), np.asarray(gain_o),
                                      err_msg=force)
        np.testing.assert_array_equal(np.asarray(can_p), np.asarray(can_p_o),
                                      err_msg=force)
