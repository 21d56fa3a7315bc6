"""Fused serving engine tests: batched-gate parity, the hoisted / warm-started
/ sharded CCG, top-k bandwidth repair convergence, and the whole-run
``serve_scan`` driver vs the host-loop ``run_batch``."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import SystemConfig
from repro.core.features import feature_dim
from repro.core.gating import (
    GateConfig,
    gate_specs,
    gate_step,
    gate_step_batch,
    init_batch_state,
    init_state,
)
from repro.core.robust import RobustProblem, solve_ccg, solve_ccg_sharded
from repro.core.router import RouterEngine, enforce_bandwidth, init_router_state, route_scan, route_step
from repro.launch.mesh import make_host_mesh
from repro.models.params import init_params
from repro.serving.scan import run_scan
from repro.serving.simulator import SimConfig, Simulator
from repro.sharding.compat import make_mesh

SYS = SystemConfig()
PROB = RobustProblem.build(SYS)
LAT = PROB.lat


# ---------------------------------------------------------------------------
# Fused batched gate vs the looped per-stream oracle
# ---------------------------------------------------------------------------
def _gate_setup(m=5, d=8, hid=16, window=4, seed=0):
    cfg = GateConfig(d_feature=d, d_hidden=hid, var_window=window)
    p = init_params(gate_specs(cfg), jax.random.PRNGKey(seed))
    return cfg, p


def _looped_reference(cfg, p, dxs):
    """vmap-free oracle: gate_step per stream per step. dxs: (S, M, d)."""
    steps, m, _ = dxs.shape
    states = [init_state(cfg) for _ in range(m)]
    taus = np.zeros((steps, m))
    gs = np.zeros((steps, m))
    for t in range(steps):
        for i in range(m):
            states[i], (tau, g) = gate_step(cfg, p, states[i], dxs[t, i])
            taus[t, i] = float(tau)
            gs[t, i] = float(g)
    return taus, gs, states


def test_gate_step_batch_matches_looped_gate_step():
    """Incremental-variance fused step == per-stream loop over a multi-step
    sequence that wraps the ring buffer (steps > var_window)."""
    cfg, p = _gate_setup(window=4)
    steps = 11  # > var_window: exercises eviction/wraparound
    dxs = jax.random.normal(jax.random.PRNGKey(2), (steps, 5, cfg.d_feature))
    taus_ref, gs_ref, states_ref = _looped_reference(cfg, p, dxs)

    st = init_batch_state(cfg, 5)
    taus = np.zeros((steps, 5))
    gs = np.zeros((steps, 5))
    for t in range(steps):
        st, (tau, g) = gate_step_batch(cfg, p, st, dxs[t])
        taus[t] = np.asarray(tau)
        gs[t] = np.asarray(g)
    np.testing.assert_allclose(taus, taus_ref, atol=1e-5)
    np.testing.assert_allclose(gs, gs_ref, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(st.h), np.stack([s.h for s in states_ref]), atol=1e-5)
    assert np.all(np.asarray(st.var_idx) == steps)
    # the incremental running sums agree with a fresh scan of the buffer
    np.testing.assert_allclose(
        np.asarray(st.var_sum), np.asarray(st.var_buf.sum(axis=1)), atol=1e-4)


def test_gate_step_batch_pallas_interpret_parity():
    """The Pallas cell (interpret mode on CPU) matches the ref dispatch."""
    cfg, p = _gate_setup(m=4)
    dxs = jax.random.normal(jax.random.PRNGKey(7), (6, 4, cfg.d_feature))
    st_ref = init_batch_state(cfg, 4)
    st_pal = init_batch_state(cfg, 4)
    for t in range(6):
        st_ref, (tau_r, _) = gate_step_batch(cfg, p, st_ref, dxs[t], force="ref")
        st_pal, (tau_p, _) = gate_step_batch(cfg, p, st_pal, dxs[t], force="interpret")
        np.testing.assert_allclose(np.asarray(tau_p), np.asarray(tau_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_pal.h), np.asarray(st_ref.h), atol=1e-5)


def test_gate_cell_pads_odd_batches():
    """Pallas dispatch pads B up to the block size, so any batch works."""
    from repro.kernels.temporal_gate.ops import gate_cell

    cfg, p = _gate_setup()
    b = 5
    dx = jax.random.normal(jax.random.PRNGKey(1), (b, cfg.d_feature))
    h = jax.random.normal(jax.random.PRNGKey(2), (b, cfg.d_hidden)) * 0.1
    vol = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (b,)))
    got = gate_cell(dx, h, vol, p, block_b=4, force="interpret")
    want = gate_cell(dx, h, vol, p, force="ref")
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


# ---------------------------------------------------------------------------
# Hoisted / warm-started / sharded CCG
# ---------------------------------------------------------------------------
def test_solve_ccg_sharded_matches_dense():
    """shard_map on the host mesh returns identical decisions + bounds.

    The host mesh has a size-1 data axis; the real multi-shard + padding
    path is covered by ``test_solve_ccg_sharded_multidevice`` below.
    """
    mesh = make_host_mesh()
    rng = np.random.default_rng(42)
    for m in (8, 13):
        z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
        aq = jnp.asarray(rng.uniform(0.5, 0.75, m), jnp.float32)
        sol = solve_ccg(PROB, z, aq)
        sol_s = solve_ccg_sharded(PROB, z, aq, mesh)
        assert set(sol) == set(sol_s)
        for k in sol:
            np.testing.assert_array_equal(np.asarray(sol[k]), np.asarray(sol_s[k]))


def test_solve_ccg_sharded_multidevice():
    """4 fake host devices, M=13 (pad to 16): decisions identical to dense.

    Runs in a subprocess (device count locks at first jax init — same idiom
    as tests/test_pipeline.py)."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.sharding.compat import make_mesh
        import jax.numpy as jnp
        import numpy as np
        from repro.core.cost_model import SystemConfig
        from repro.core.robust import RobustProblem, solve_ccg, solve_ccg_sharded

        prob = RobustProblem.build(SystemConfig())
        mesh = make_mesh((4,), ("data",))
        rng = np.random.default_rng(42)
        for m in (13, 16):  # 13: padding path; 16: exact split
            z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
            aq = jnp.asarray(rng.uniform(0.5, 0.75, m), jnp.float32)
            sol = solve_ccg(prob, z, aq)
            sol_s = solve_ccg_sharded(prob, z, aq, mesh)
            for k in sol:
                np.testing.assert_array_equal(np.asarray(sol[k]), np.asarray(sol_s[k]))
        print("OK")
        """
    )
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_solve_ccg_warm_start_preserves_decisions_fewer_iters():
    """Seeding the scenario set with a feasible warm start must not change
    the converged decisions and can only reduce CCG iterations."""
    rng = np.random.default_rng(1234)
    z = jnp.asarray(rng.uniform(0, 1, 16), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.75, 16), jnp.float32)
    cold = solve_ccg(PROB, z, aq)
    warm_y = LAT.flatten_index(cold["route"], cold["r"], cold["p"]).astype(jnp.int32)
    warm = solve_ccg(PROB, z, aq, warm_y=warm_y)
    for k in ("route", "r", "p", "v"):
        np.testing.assert_array_equal(np.asarray(cold[k]), np.asarray(warm[k]))
    np.testing.assert_allclose(np.asarray(cold["o_up"]), np.asarray(warm["o_up"]),
                               rtol=1e-6)
    assert np.all(np.asarray(warm["iters"]) <= np.asarray(cold["iters"]))
    assert np.asarray(warm["iters"]).sum() < np.asarray(cold["iters"]).sum()


def test_solve_ccg_ignores_infeasible_warm_start():
    """A warm start pointing at an infeasible first-stage option must not
    corrupt the bounds (falls back to the cold init for that task)."""
    z = jnp.asarray([0.5, 0.5], jnp.float32)
    aq = jnp.asarray([0.6, 0.6], jnp.float32)
    cold = solve_ccg(PROB, z, aq)
    # y=0 is the cheapest edge config at min fps — generally infeasible here
    warm = solve_ccg(PROB, z, aq, warm_y=jnp.zeros((2,), jnp.int32))
    np.testing.assert_allclose(np.asarray(cold["o_up"]), np.asarray(warm["o_up"]),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Top-k bandwidth repair
# ---------------------------------------------------------------------------
def test_enforce_bandwidth_topk_converges_in_few_rounds():
    """Multi-task demotion clears the budget in ~#fidelity-levels rounds even
    for a large batch (the scalar one-per-round repair needed O(M) rounds)."""
    m = 48
    rng = np.random.default_rng(7)
    z = jnp.asarray(rng.uniform(0.1, 0.6, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.6, m), jnp.float32)
    sol = {
        "route": jnp.zeros((m,), jnp.int32),
        "r": jnp.full((m,), SYS.n_res - 1, jnp.int32),
        "p": jnp.full((m,), SYS.n_fps - 1, jnp.int32),
        "v": jnp.full((m,), SYS.num_versions - 1, jnp.int32),
    }
    start_bw = float(np.asarray(LAT.solution_bandwidth(sol)).sum())
    budget = 0.6 * start_bw
    fixed, _ = enforce_bandwidth(SYS, sol, z, aq, total_budget=budget, rounds=8)
    final_bw = float(np.asarray(LAT.solution_bandwidth(fixed)).sum())
    assert final_bw <= budget + 1e-6, (final_bw, budget)


# ---------------------------------------------------------------------------
# Scan drivers
# ---------------------------------------------------------------------------
def test_route_scan_matches_sequential_route_step():
    """One lax.scan over S segments == S sequential route_step calls."""
    m, s = 6, 5
    rng = np.random.default_rng(3)
    gcfg = GateConfig(d_feature=feature_dim())
    gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.7, m), jnp.float32)
    dx_seq = jnp.asarray(rng.normal(size=(s, m, feature_dim())), jnp.float32)

    state = init_router_state(gcfg, m)
    seq_sols = []
    for t in range(s):
        state, sol = route_step(PROB, gcfg, gparams, state, dx_seq[t], z, aq)
        seq_sols.append(sol)

    state2 = init_router_state(gcfg, m)
    state2, sols = route_scan(PROB, gcfg, gparams, state2, dx_seq, z, aq)
    for k in ("route", "r", "p", "v"):
        want = np.stack([np.asarray(s_[k]) for s_ in seq_sols])
        np.testing.assert_array_equal(np.asarray(sols[k]), want)
    np.testing.assert_allclose(
        np.asarray(sols["tau"]),
        np.stack([np.asarray(s_["tau"]) for s_ in seq_sols]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(state2.prev_route),
                                  np.asarray(state.prev_route))


def test_serve_scan_matches_host_loop_metrics():
    """The whole-run compiled driver reproduces a host loop driving the
    RouterEngine round by round on a fixed seed (same rounds, same noise
    draw) — the R2E-VID path's host-loop oracle."""
    scfg = SimConfig(n_rounds=5, n_tasks=16, seed=7, bw_fluctuation=0.15)
    gcfg = GateConfig(d_feature=feature_dim())
    gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))

    sim_a = Simulator(SYS, scfg)
    out_a = run_scan(sim_a, gcfg, gparams, feature_seed=0)

    sim_b = Simulator(SYS, scfg)
    frng = np.random.default_rng(0)
    dx_seq = jnp.asarray(
        frng.normal(size=(scfg.n_rounds, scfg.n_tasks, feature_dim())), jnp.float32)
    engine = RouterEngine(PROB, gcfg, gparams, n_streams=scfg.n_tasks)
    rnds, cfgs = [], []
    for i in range(scfg.n_rounds):
        rnd = sim_b.sample_round()
        sol = engine.step(dx_seq[i], jnp.asarray(rnd["z"]), jnp.asarray(rnd["aq"]))
        rnds.append(rnd)
        cfgs.append({k: np.asarray(sol[k]) for k in ("route", "r", "p", "v")})
    met = sim_b.realize_batch(rnds, cfgs)
    out_b = {k: float(met[k].mean(axis=1).mean())
             for k in ("delay", "energy", "cost", "accuracy", "success")}
    out_b["cloud_frac"] = float(met["route"].mean(axis=1).mean())
    assert set(out_a) == set(out_b)
    for k in out_a:
        np.testing.assert_allclose(out_a[k], out_b[k], atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# Unrolled masked CCG vs the while_loop oracle
# ---------------------------------------------------------------------------
def _assert_ccg_identical(sol_a, sol_b, msg=""):
    assert set(sol_a) == set(sol_b)
    for k in sol_a:
        np.testing.assert_array_equal(
            np.asarray(sol_a[k]), np.asarray(sol_b[k]), err_msg=f"{msg}:{k}")


def test_unrolled_ccg_matches_while_loop():
    """Fixed-unroll masked iteration == per-task while_loop: decisions,
    bounds, and iteration counts bit-identical on a mixed random batch,
    cold and warm-started."""
    from repro.core.robust import solve_ccg_while

    rng = np.random.default_rng(99)
    z = jnp.asarray(rng.uniform(0, 1, 37), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.75, 37), jnp.float32)
    cold_u = solve_ccg(PROB, z, aq)
    cold_w = solve_ccg_while(PROB, z, aq)
    _assert_ccg_identical(cold_u, cold_w, "cold")

    warm_y = LAT.flatten_index(cold_w["route"], cold_w["r"], cold_w["p"])
    warm_u = solve_ccg(PROB, z, aq, warm_y=warm_y.astype(jnp.int32))
    warm_w = solve_ccg_while(PROB, z, aq, warm_y=warm_y.astype(jnp.int32))
    _assert_ccg_identical(warm_u, warm_w, "warm")


def test_unrolled_ccg_matches_while_loop_adversarial():
    """Adversarial lanes: a warm start pointing at an infeasible option
    (warm miss), a task no configuration can satisfy (margin fallback), and
    easy tasks mixed in — all bit-identical to the while_loop solver."""
    from repro.core.robust import solve_ccg_while

    z = jnp.asarray([0.5, 0.9, 0.05, 0.7], jnp.float32)
    aq = jnp.asarray([0.6, 0.99, 0.5, 0.65], jnp.float32)   # task 1 infeasible
    # task 0: warm miss (y=0 is the cheapest, generally infeasible config);
    # task 1: warm miss on an all-infeasible task; others: no warm start
    warm_y = jnp.asarray([0, 0, -1, -1], jnp.int32)
    sol_u = solve_ccg(PROB, z, aq, warm_y=warm_y)
    sol_w = solve_ccg_while(PROB, z, aq, warm_y=warm_y)
    _assert_ccg_identical(sol_u, sol_w, "adversarial")
    assert np.asarray(sol_u["infeasible"]).tolist() == [False, True, False, False]


def test_unrolled_ccg_matches_while_loop_p1_degenerate():
    """Γ=0 leaves a single (all-zero) pole: the unroll collapses to
    min(max_iters, 2) steps and must still match the while_loop solver."""
    from repro.core.robust import solve_ccg_while

    prob1 = RobustProblem.build(SystemConfig(gamma=0))
    assert prob1.poles.shape[0] == 1
    rng = np.random.default_rng(3)
    z = jnp.asarray(rng.uniform(0, 1, 11), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.75, 11), jnp.float32)
    _assert_ccg_identical(
        solve_ccg(prob1, z, aq), solve_ccg_while(prob1, z, aq), "p1")
    assert int(np.asarray(solve_ccg(prob1, z, aq)["iters"]).max()) <= 2


def test_unrolled_ccg_slab_master_paths_identical():
    """The slab-master op (ref and Pallas-interpret) and the incremental-η
    jnp master produce identical solutions — the three master
    implementations are interchangeable."""
    rng = np.random.default_rng(17)
    z = jnp.asarray(rng.uniform(0, 1, 19), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.75, 19), jnp.float32)
    auto = solve_ccg(PROB, z, aq)
    _assert_ccg_identical(auto, solve_ccg(PROB, z, aq, force="ref"), "ref")
    _assert_ccg_identical(auto, solve_ccg(PROB, z, aq, force="interpret"), "interpret")


# ---------------------------------------------------------------------------
# End-to-end sharded serve_scan
# ---------------------------------------------------------------------------
def test_serve_scan_accepts_host_mesh():
    """On the 1-device host mesh the sharded path must agree with dense."""
    from repro.core.robust import RobustProblem as RP
    from repro.serving.scan import serve_scan

    m, r = 6, 3
    rng = np.random.default_rng(21)
    gcfg = GateConfig(d_feature=feature_dim())
    gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))
    mesh = make_mesh((jax.device_count(),), ("data",))
    dx = jnp.asarray(rng.normal(size=(r, m, feature_dim())), jnp.float32)
    z = jnp.asarray(rng.uniform(0, 1, (r, m)), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.7, (r, m)), jnp.float32)
    bwm = jnp.asarray(rng.uniform(0.8, 1.0, (r, 2)), jnp.float32)
    u = jnp.asarray(rng.uniform(0, 0.3, (r, 5)), jnp.float32)

    st_a, met_a = serve_scan(PROB, gcfg, gparams, init_router_state(gcfg, m),
                             dx, z, aq, bwm, u)
    st_b, met_b = serve_scan(PROB, gcfg, gparams, init_router_state(gcfg, m),
                             dx, z, aq, bwm, u, mesh=mesh)
    assert set(met_a) == set(met_b)
    for k in met_a:
        np.testing.assert_allclose(np.asarray(met_a[k]), np.asarray(met_b[k]),
                                   atol=1e-5, err_msg=k)
    np.testing.assert_array_equal(np.asarray(st_a.prev_route),
                                  np.asarray(st_b.prev_route))


def test_serve_scan_sharded_multidevice():
    """4 fake host devices: the whole-run sharded scan (gate + Stage-1 +
    unrolled CCG sharded over streams, C6 + realization on the gathered real
    batch) reproduces the dense metrics and final state for M=13 (padding:
    13 streams over 4 devices) and M=16 (exact split).  Subprocess because
    the device count locks at first jax init."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.sharding.compat import make_mesh
        import jax.numpy as jnp
        import numpy as np
        from repro.core.cost_model import SystemConfig
        from repro.core.features import feature_dim
        from repro.core.gating import GateConfig, gate_specs
        from repro.core.robust import RobustProblem
        from repro.core.router import init_router_state
        from repro.models.params import init_params
        from repro.serving.scan import serve_scan

        prob = RobustProblem.build(SystemConfig())
        gcfg = GateConfig(d_feature=feature_dim())
        gp = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))
        mesh = make_mesh((4,), ("data",))
        for m in (13, 16):  # 13: padding path; 16: exact split
            rng = np.random.default_rng(m)
            r = 4
            dx = jnp.asarray(rng.normal(size=(r, m, feature_dim())), jnp.float32)
            z = jnp.asarray(rng.uniform(0, 1, (r, m)), jnp.float32)
            aq = jnp.asarray(rng.uniform(0.5, 0.7, (r, m)), jnp.float32)
            bwm = jnp.asarray(rng.uniform(0.8, 1.0, (r, 2)), jnp.float32)
            u = jnp.asarray(rng.uniform(0, 0.3, (r, 5)), jnp.float32)
            st_a, met_a = serve_scan(prob, gcfg, gp, init_router_state(gcfg, m),
                                     dx, z, aq, bwm, u)
            st_b, met_b = serve_scan(prob, gcfg, gp, init_router_state(gcfg, m),
                                     dx, z, aq, bwm, u, mesh=mesh)
            assert set(met_a) == set(met_b)
            for k in met_a:
                np.testing.assert_allclose(
                    np.asarray(met_a[k]), np.asarray(met_b[k]), atol=1e-5,
                    err_msg=f"M={m}:{k}")
            np.testing.assert_array_equal(np.asarray(st_a.prev_route),
                                          np.asarray(st_b.prev_route))
            np.testing.assert_allclose(np.asarray(st_a.gate.h),
                                       np.asarray(st_b.gate.h), atol=1e-5)
        print("OK")
        """
    )
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
