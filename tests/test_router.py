"""Router-layer tests: decision lattice, C6 bandwidth repair, the temporal-
consistency constraint, the streaming engine, and vectorized realization."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import SystemConfig, accuracy_table, cost_tables
from repro.core.features import feature_dim
from repro.core.gating import GateConfig, gate_specs
from repro.core.lattice import DecisionLattice, version_deviations
from repro.core.robust import RobustProblem, exact_oracle, solve_ccg
from repro.core.router import (
    RouterConfig,
    RouterEngine,
    apply_temporal_consistency,
    enforce_bandwidth,
    init_router_state,
    route_step,
    stage1_configure,
)
from repro.models.params import init_params

SYS = SystemConfig()
PROB = RobustProblem.build(SYS)
LAT = PROB.lat


# ---------------------------------------------------------------------------
# DecisionLattice
# ---------------------------------------------------------------------------
def test_lattice_index_roundtrip():
    """flatten∘unflatten = id over the full F index space, and back."""
    ys = jnp.arange(LAT.n_flat)
    route, r, p = LAT.unflatten_index(ys)
    assert np.all(np.asarray(LAT.flatten_index(route, r, p)) == np.asarray(ys))
    # all (route, r, p) triples map to distinct flat indices in range
    rt, rr, pp = np.meshgrid(np.arange(2), np.arange(SYS.n_res), np.arange(SYS.n_fps),
                             indexing="ij")
    flat = np.asarray(LAT.flatten_index(rt.ravel(), rr.ravel(), pp.ravel()))
    assert sorted(flat.tolist()) == list(range(LAT.n_flat))


def test_lattice_flat_tables_match_natural_layout():
    c1, b2, bw = cost_tables(SYS)
    ys = jnp.arange(LAT.n_flat)
    route, r, p = LAT.unflatten_index(ys)
    np.testing.assert_allclose(np.asarray(LAT.c1_flat), np.asarray(c1)[r, p, route])
    np.testing.assert_allclose(np.asarray(LAT.b2_flat), np.asarray(b2)[r, p, :, route])
    np.testing.assert_allclose(np.asarray(LAT.bw_flat), np.asarray(bw)[r, p, route])


def test_lattice_accuracy_flat_matches_table():
    z = jnp.asarray([0.1, 0.6, 0.95], jnp.float32)
    f = np.asarray(accuracy_table(SYS, z))
    f_flat = np.asarray(LAT.accuracy_flat(z))
    ys = np.arange(LAT.n_flat)
    route, r, p = LAT.unflatten_index(ys)
    np.testing.assert_allclose(f_flat, f[:, r, p, :, route].transpose(1, 0, 2))


def test_lattice_build_is_cached():
    assert DecisionLattice.build(SYS) is DecisionLattice.build(SystemConfig())


def test_version_deviations_monotone():
    u = np.asarray(version_deviations(SYS))
    assert u.shape == (SYS.num_versions,)
    assert np.all(np.diff(u) > 0)  # bigger models deviate more
    assert np.isclose(u[-1], SYS.u_dev)


# ---------------------------------------------------------------------------
# Solver parity on a fixed seed (pre-refactor golden decisions)
# ---------------------------------------------------------------------------
def test_solver_parity_fixed_seed_golden():
    """Refactored solve_ccg reproduces the pre-lattice solver's decisions and
    matches exact_oracle objectives on a fixed seed."""
    rng = np.random.default_rng(1234)
    z = jnp.asarray(rng.uniform(0, 1, 12), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.75, 12), jnp.float32)
    sol = solve_ccg(PROB, z, aq)
    # golden decisions captured from the pre-refactor solver on this seed
    assert np.asarray(sol["route"]).tolist() == [0] * 12
    assert np.asarray(sol["r"]).tolist() == [4, 4, 4, 2, 3, 1, 1, 4, 3, 3, 3, 3]
    assert np.asarray(sol["p"]).tolist() == [3, 1, 1, 0, 0, 0, 0, 1, 0, 0, 0, 0]
    assert np.asarray(sol["v"]).tolist() == [4, 4, 4, 4, 4, 3, 3, 4, 2, 4, 4, 4]
    np.testing.assert_allclose(
        np.asarray(sol["o_up"]),
        [2.2345657348632812, 1.1172828674316406, 1.1172828674316406,
         0.24828505516052246, 0.3879454433917999, 0.07857239246368408,
         0.07857239246368408, 1.1172828674316406, 0.13119734823703766,
         0.3879454433917999, 0.3879454433917999, 0.3879454433917999],
        rtol=1e-6,
    )
    y, obj = exact_oracle(PROB, z, aq)
    np.testing.assert_allclose(np.asarray(sol["o_up"]), np.asarray(obj), rtol=1e-6)
    y_sol = np.asarray(LAT.flatten_index(sol["route"], sol["r"], sol["p"]))
    assert np.all(y_sol == np.asarray(y))


# ---------------------------------------------------------------------------
# C6 bandwidth repair
# ---------------------------------------------------------------------------
def _inflated_solution(m=8, seed=0):
    """A deliberately over-provisioned solution (max fidelity, biggest model):
    lots of accuracy slack, so demotions are possible.  A CCG solution is
    already cost-minimal — i.e. at the feasibility frontier — so repair is a
    no-op on it; the repair mechanism only bites on slack-carrying configs."""
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.uniform(0.1, 0.6, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.6, m), jnp.float32)
    sol = {
        "route": jnp.zeros((m,), jnp.int32),
        "r": jnp.full((m,), SYS.n_res - 1, jnp.int32),
        "p": jnp.full((m,), SYS.n_fps - 1, jnp.int32),
        "v": jnp.full((m,), SYS.num_versions - 1, jnp.int32),
    }
    return z, aq, sol


def test_enforce_bandwidth_meets_budget_when_feasible():
    z, aq, sol = _inflated_solution()
    # sums in the repair's own float32 reduction order: numpy's SIMD
    # pairwise sum rounds differently, by an ulp at this size
    start_bw = float(LAT.solution_bandwidth(sol).sum())
    budget = 0.5 * start_bw
    fixed, bw_hist = enforce_bandwidth(SYS, sol, z, aq, total_budget=budget, rounds=64)
    final_bw = float(LAT.solution_bandwidth(fixed).sum())
    assert final_bw <= budget + 1e-6, (final_bw, budget)
    # the draw shrinks monotonically round over round
    assert np.all(np.diff(np.asarray(bw_hist)) <= 1e-6)


def test_enforce_bandwidth_demoted_tasks_stay_feasible():
    z, aq, sol = _inflated_solution(seed=3)
    start_bw = float(np.asarray(LAT.solution_bandwidth(sol)).sum())
    fixed, _ = enforce_bandwidth(SYS, sol, z, aq, total_budget=0.5 * start_bw, rounds=64)
    f = np.asarray(accuracy_table(SYS, z))
    idx = np.arange(len(np.asarray(fixed["r"])))
    acc = f[idx, np.asarray(fixed["r"]), np.asarray(fixed["p"]),
            np.asarray(fixed["v"]), np.asarray(fixed["route"])]
    margin = SYS.acc_margin_robust
    assert np.all(acc >= np.asarray(aq) + margin - 1e-6)


def test_enforce_bandwidth_noop_when_under_budget():
    z, aq, sol = _inflated_solution(seed=1)
    start_bw = float(np.asarray(LAT.solution_bandwidth(sol)).sum())
    fixed, _ = enforce_bandwidth(SYS, sol, z, aq, total_budget=2.0 * start_bw, rounds=16)
    assert np.all(np.asarray(fixed["r"]) == np.asarray(sol["r"]))
    assert np.all(np.asarray(fixed["p"]) == np.asarray(sol["p"]))


def test_enforce_bandwidth_noop_on_ccg_solution():
    """CCG solutions are cost-minimal, hence at the feasibility frontier: no
    single demotion stays feasible, so repair cannot (and must not) move them."""
    rng = np.random.default_rng(0)
    m = 20
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.7, m), jnp.float32)
    sol = solve_ccg(PROB, z, aq)
    start_bw = float(np.asarray(LAT.solution_bandwidth(sol)).sum())
    fixed, _ = enforce_bandwidth(SYS, sol, z, aq, total_budget=0.5 * start_bw, rounds=32)
    final_bw = float(np.asarray(LAT.solution_bandwidth(fixed)).sum())
    assert final_bw <= start_bw + 1e-6


# ---------------------------------------------------------------------------
# Temporal-consistency constraint
# ---------------------------------------------------------------------------
def test_temporal_consistency_suppresses_and_allows_flips():
    rcfg = RouterConfig(delta0=0.0, delta1=4.0)
    prev_route = jnp.asarray([0, 0, 1, -1], jnp.int32)
    prev_tau = jnp.asarray([0.5, 0.5, 0.5, 0.5], jnp.float32)
    #            small Δτ   large Δτ   small Δτ   no history
    taus = jnp.asarray([0.6, 0.9, 0.55, 0.6], jnp.float32)
    want = jnp.asarray([1, 1, 0, 1], jnp.int32)  # desired routes (all flips)
    out = np.asarray(apply_temporal_consistency(want, prev_route, taus, prev_tau, rcfg))
    # |Δτ|·δ1 = 0.4 < 1 -> flip suppressed; 1.6 >= 1 -> allowed; first segment free
    assert out.tolist() == [0, 1, 1, 1]


def test_stage1_first_segment_ignores_history():
    m = 3
    taus = jnp.asarray([0.9, 0.9, 0.1], jnp.float32)
    z = jnp.asarray([0.3, 0.3, 0.3], jnp.float32)
    # A^q low enough that the smallest edge model is Stage-1 feasible
    aq = jnp.asarray([0.5, 0.5, 0.5], jnp.float32)
    prev_route = -jnp.ones((m,), jnp.int32)
    prev_tau = jnp.zeros((m,), jnp.float32)
    route, r = stage1_configure(SYS, taus, z, aq, prev_route, prev_tau)
    # high tau -> cloud, low tau -> edge; no suppression without history
    assert np.asarray(route).tolist() == [1, 1, 0]


def test_stage1_flip_suppressed_with_history():
    m = 2
    taus = jnp.asarray([0.9, 0.9], jnp.float32)  # both want cloud
    z = jnp.asarray([0.3, 0.3], jnp.float32)
    aq = jnp.asarray([0.5, 0.5], jnp.float32)
    prev_route = jnp.asarray([0, 0], jnp.int32)
    # task 0: tau barely moved -> flip suppressed; task 1: big move -> allowed
    prev_tau = jnp.asarray([0.85, 0.3], jnp.float32)
    route, _ = stage1_configure(SYS, taus, z, aq, prev_route, prev_tau)
    assert np.asarray(route).tolist() == [0, 1]


# ---------------------------------------------------------------------------
# Streaming engine
# ---------------------------------------------------------------------------
def test_route_step_threads_state_and_matches_solver():
    m = 8
    rng = np.random.default_rng(5)
    gcfg = GateConfig(d_feature=feature_dim())
    gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.7, m), jnp.float32)
    state = init_router_state(gcfg, m)
    assert np.all(np.asarray(state.prev_route) == -1)

    dx = jnp.asarray(rng.normal(size=(m, feature_dim())), jnp.float32)
    state, sol = route_step(PROB, gcfg, gparams, state, dx, z, aq)
    # state advanced: history recorded, gate recurrence progressed
    assert np.all(np.asarray(state.prev_route) == np.asarray(sol["route"]))
    np.testing.assert_allclose(np.asarray(state.prev_tau), np.asarray(sol["tau"]))
    assert np.all(np.asarray(state.gate.var_idx) == 1)
    for key in ("route", "r", "p", "v", "tau", "warm_route", "warm_r"):
        assert key in sol

    # a second step sees the first step's routes as history
    state2, sol2 = route_step(PROB, gcfg, gparams, state, dx * 0.9, z, aq)
    assert np.all(np.asarray(state2.gate.var_idx) == 2)
    allowed = np.abs(np.asarray(sol2["tau"]) - np.asarray(sol["tau"])) * 4.0 >= 1.0
    flipped = np.asarray(sol2["route"]) != np.asarray(sol["route"])
    assert not np.any(flipped & ~allowed), "forbidden route flip leaked through"


def test_router_engine_steady_state_routes_under_budget():
    m = 16
    rng = np.random.default_rng(11)
    gcfg = GateConfig(d_feature=feature_dim())
    gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(1))
    engine = RouterEngine(PROB, gcfg, gparams, n_streams=m)
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.7, m), jnp.float32)
    for _ in range(4):
        dx = jnp.asarray(rng.normal(size=(m, feature_dim())), jnp.float32)
        sol = engine.step(dx, z, aq)
    bw = float(np.asarray(LAT.solution_bandwidth(sol)).sum())
    assert bw <= SYS.total_bw_mbps + 1e-6
    engine.reset()
    assert np.all(np.asarray(engine.state.prev_route) == -1)


# ---------------------------------------------------------------------------
# Vectorized realization parity
# ---------------------------------------------------------------------------
def test_vectorized_realize_matches_loop_reference():
    from repro.serving.baselines import make_method
    from repro.serving.simulator import SimConfig, Simulator

    sim = Simulator(SYS, SimConfig(n_tasks=64, seed=9, bw_fluctuation=0.2,
                                   requirement="fluctuating"))
    method = make_method("JCAB", SYS)
    state = {}
    for _ in range(3):
        rnd = sim.sample_round()
        cfg = method(rnd, state)
        noise = np.zeros(64)
        met_v = sim._realize_deterministic(rnd, cfg)
        met_r = sim.realize_reference(rnd, cfg, noise=noise)
        for k in ("delay", "energy", "cost", "accuracy"):
            np.testing.assert_allclose(met_v[k], met_r[k], atol=1e-4, rtol=1e-4)


def test_realize_batch_matches_per_round_realize():
    from repro.serving.baselines import make_method
    from repro.serving.simulator import SimConfig, Simulator

    sim = Simulator(SYS, SimConfig(n_tasks=32, seed=2, bw_fluctuation=0.1))
    method = make_method("RDAP", SYS)
    state = {}
    rnds, cfgs, singles = [], [], []
    for _ in range(4):
        rnd = sim.sample_round()
        cfg = method(rnd, state)
        rnds.append(rnd)
        cfgs.append(cfg)
        singles.append(sim._realize_deterministic(rnd, cfg))
    batched = sim.realize_batch(rnds, cfgs)
    for k in ("delay", "energy", "cost"):
        got = batched[k]
        want = np.stack([s[k] for s in singles])
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Table-free hot path: bit-parity vs the table-based goldens
# ---------------------------------------------------------------------------
def test_stage1_accuracy_pointwise_matches_table_slice():
    """``accuracy_stage1`` == the f[:, :, -1, 0, 0] slice of the broadcast
    table, bitwise — Stage-1 decisions cannot drift off the table path."""
    from repro.core.cost_model import accuracy_stage1

    z = jnp.asarray(np.random.default_rng(0).uniform(0, 1, 33), jnp.float32)
    table_slice = np.asarray(accuracy_table(SYS, z))[:, :, -1, 0, 0]
    pointwise = np.asarray(accuracy_stage1(SYS, z))
    np.testing.assert_array_equal(pointwise, table_slice)


def _enforce_bandwidth_table_golden(lat, sol, difficulty, acc_req,
                                    total_budget=None, rounds=8):
    """The pre-table-free C6 repair (builds the (M, N, Z, K, 2) accuracy
    table + fancy-index gathers) — kept verbatim as the parity golden."""
    from repro.core.robust import BIG

    sys = lat.sys
    bw_tab = lat.bw
    f = lat.accuracy(difficulty)
    budget = sys.total_bw_mbps if total_budget is None else total_budget
    margin = sys.acc_margin_robust
    m = sol["r"].shape[0]

    def round_fn(state, _):
        r, p = state
        bw = bw_tab[r, p, sol["route"]]
        excess = bw.sum() - budget
        p_dn = jnp.maximum(p - 1, 0)
        r_dn = jnp.maximum(r - 1, 0)
        f_pdn = f[jnp.arange(m), r, p_dn, sol["v"], sol["route"]]
        f_rdn = f[jnp.arange(m), r_dn, p, sol["v"], sol["route"]]
        can_p = (p > 0) & (f_pdn >= acc_req + margin)
        can_r = (r > 0) & (f_rdn >= acc_req + margin)
        gain_p = bw - bw_tab[r, p_dn, sol["route"]]
        gain_r = bw - bw_tab[r_dn, p, sol["route"]]
        gain = jnp.where(can_p, gain_p, jnp.where(can_r, gain_r, -BIG))
        order = jnp.argsort(-gain)
        gain_sorted = gain[order]
        cum_before = jnp.concatenate(
            [jnp.zeros((1,), gain.dtype), jnp.cumsum(gain_sorted)[:-1]])
        demote_sorted = (excess > 0) & (cum_before < excess) & (gain_sorted > 0)
        demote = jnp.zeros((m,), bool).at[order].set(demote_sorted)
        r = jnp.where(demote & ~can_p, r_dn, r)
        p = jnp.where(demote & can_p, p_dn, p)
        return (r, p), excess + budget

    (r, p), bw_hist = jax.lax.scan(
        round_fn, (sol["r"], sol["p"]), None, length=rounds)
    return dict(sol, r=r, p=p), bw_hist


def test_enforce_bandwidth_table_free_matches_table_golden():
    """Pointwise-accuracy + hoisted-panel C6 repair == the table-building
    golden, bit for bit (decisions AND the bandwidth history), across easy
    and tight budgets."""
    m = 41
    rng = np.random.default_rng(11)
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.75, m), jnp.float32)
    sol = solve_ccg(PROB, z, aq)
    sol = {k: sol[k] for k in ("route", "r", "p", "v")}
    start_bw = float(np.asarray(LAT.solution_bandwidth(sol)).sum())
    for frac in (2.0, 0.6, 0.25):   # no-op, moderate, aggressive demotion
        budget = frac * start_bw
        got, got_hist = enforce_bandwidth(LAT, sol, z, aq, total_budget=budget)
        want, want_hist = _enforce_bandwidth_table_golden(
            LAT, sol, z, aq, total_budget=budget)
        for k in got:
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(want[k]),
                err_msg=f"frac={frac}:{k}")
        np.testing.assert_array_equal(
            np.asarray(got_hist), np.asarray(want_hist), err_msg=f"frac={frac}")


def _enforce_bandwidth_gather_golden(sys_or_lat, sol, difficulty, acc_req,
                                     total_budget=None, rounds: int = 8,
                                     force: str = "auto", task_mask=None):
    """The C6 repair with the per-pass draw gather, ``gain[order]`` and the
    scatter back to task order — kept verbatim as the parity golden of the
    gather-free loop; it also emits each pass's ``demote_sorted`` (all False
    in a skipped pass) so the test can check that it is a prefix."""
    from repro.core.cost_model import fps_norm, res_norm
    from repro.core.router import _as_lattice
    from repro.kernels.c6_tail.ops import c6_tail

    lat = _as_lattice(sys_or_lat)
    sys = lat.sys
    budget = sys.total_bw_mbps if total_budget is None else total_budget

    m = sol["r"].shape[0]
    nz = sys.n_fps
    bw_panel = jnp.moveaxis(lat.bw, -1, 0)[sol["route"]]   # (M, N, Z)
    bw_panel = bw_panel.reshape(bw_panel.shape[0], -1)     # (M, N·Z)
    _take_bw = lambda r, p: jnp.take_along_axis(
        bw_panel, (r * nz + p)[:, None], axis=1)[:, 0]
    if task_mask is None:
        take_bw = _take_bw
    else:
        take_bw = lambda r, p: jnp.where(task_mask, _take_bw(r, p), 0.0)
    z = jnp.asarray(difficulty, jnp.float32)
    acc_thr = jnp.asarray(acc_req, jnp.float32) + sys.acc_margin_robust
    rn = res_norm(sys)
    pn = fps_norm(sys)

    def round_fn(state, _):
        r, p, active = state
        bw = take_bw(r, p)
        excess = bw.sum() - budget

        def demote_round(rp):
            r, p = rp
            _, gain, can_p = c6_tail(
                bw_panel, r, p, sol["v"], sol["route"], z, acc_thr, rn, pn,
                n_fps=nz, force=force)
            if task_mask is not None:
                gain = jnp.where(task_mask, gain, 0.0)
            p_dn = jnp.maximum(p - 1, 0)
            r_dn = jnp.maximum(r - 1, 0)
            order = jnp.argsort(-gain)
            gain_sorted = gain[order]
            cum_before = jnp.concatenate(
                [jnp.zeros((1,), gain.dtype), jnp.cumsum(gain_sorted)[:-1]]
            )
            demote_sorted = (cum_before < excess) & (gain_sorted > 0)
            demote = jnp.zeros((m,), bool).at[order].set(demote_sorted)
            return (jnp.where(demote & ~can_p, r_dn, r),
                    jnp.where(demote & can_p, p_dn, p),
                    demote.any(), demote_sorted)

        def skip_round(rp):
            r, p = rp
            return r, p, jnp.asarray(False), jnp.zeros((m,), bool)

        r, p, progressed, demote_sorted = jax.lax.cond(
            active & (excess > 0), demote_round, skip_round, (r, p))
        return (r, p, progressed), (excess + budget, demote_sorted)

    (r, p, _), (bw_hist, demote_sorted) = jax.lax.scan(
        round_fn, (sol["r"], sol["p"], jnp.asarray(True)), None, length=rounds)
    return dict(sol, r=r, p=p), bw_hist, demote_sorted


def _fleet4096_lattice():
    """The lattice of the benchmark's ``fleet4096`` deployment."""
    import json
    from pathlib import Path

    cfg = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                      / "fleet4096.json").read_text())
    return DecisionLattice.build(SystemConfig(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in cfg["deployment"].items()}))


def _random_fleet_repair_case(lat, m: int, masked: bool, seed: int):
    """Random (route, r, p, v) over M tasks — gains are differences in a
    25-entry table, so exact gain ties are frequent — with the starting
    draw the repair sees (dead lanes draw nothing)."""
    sys = lat.sys
    rng = np.random.default_rng(seed)
    sol = {
        "route": jnp.asarray(rng.integers(0, 2, m), jnp.int32),
        "r": jnp.asarray(rng.integers(0, sys.n_res, m), jnp.int32),
        "p": jnp.asarray(rng.integers(0, sys.n_fps, m), jnp.int32),
        "v": jnp.asarray(rng.integers(0, sys.num_versions, m), jnp.int32),
    }
    z = jnp.asarray(rng.uniform(0.02, 1.0, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.8, m), jnp.float32)
    mask = jnp.asarray(rng.uniform(size=m) < 0.8) if masked else None
    draw = np.asarray(lat.solution_bandwidth(sol), np.float64)
    if masked:
        draw = draw * np.asarray(mask)
    return sol, z, aq, mask, float(draw.sum())


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
@pytest.mark.parametrize("frac", [2.0, 0.9, 0.6, 0.3])
def test_enforce_bandwidth_gather_free_matches_gather_golden(frac, masked):
    """The gather-free repair loop (one-hot draw, gains from the sort's own
    keys, threshold demotion mask) == the gather/scatter golden, bit for bit
    in (r, p) and the bandwidth history, at fleet size with and without a
    task mask; and every active pass demotes a prefix of the sorted order,
    which the threshold form relies on."""
    lat = _fleet4096_lattice()
    sol, z, aq, mask, start_bw = _random_fleet_repair_case(
        lat, 4096, masked, seed=int(frac * 10) + 100 * masked)
    budget = jnp.float32(frac * start_bw)
    got, got_hist = jax.jit(lambda s, z, aq, b, mk: enforce_bandwidth(
        lat, s, z, aq, total_budget=b, force="ref", task_mask=mk))(
            sol, z, aq, budget, mask)
    want, want_hist, demote_sorted = jax.jit(
        lambda s, z, aq, b, mk: _enforce_bandwidth_gather_golden(
            lat, s, z, aq, total_budget=b, force="ref", task_mask=mk))(
                sol, z, aq, budget, mask)
    for k in ("r", "p"):
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(got_hist), np.asarray(want_hist))
    demote_sorted = np.asarray(demote_sorted)
    n = demote_sorted.sum(axis=1)
    for k, row in enumerate(demote_sorted):
        assert row[:n[k]].all(), f"pass {k}: demoted set is not a prefix"
    if frac < 1.0:
        assert n.sum() > 0
    else:
        assert n.sum() == 0


def test_enforce_bandwidth_loop_holds_no_gather_or_scatter():
    """Structural guard: the lowered repair at fleet size holds no scatter,
    and its only gather (outside the ``c6_tail`` call, whose CPU oracle
    gathers and whose TPU kernel one-hot-folds) is the hoisted route-panel
    gather before the loop.  A gather put back into the scan body would pass
    every parity test; it fails here."""
    import re

    lat = _fleet4096_lattice()
    m = 4096
    i32 = jax.ShapeDtypeStruct((m,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((m,), jnp.float32)
    for masked in (False, True):
        mask = jax.ShapeDtypeStruct((m,), jnp.bool_) if masked else None
        text = jax.jit(lambda s, z, aq, b, mk: enforce_bandwidth(
            lat, s, z, aq, total_budget=b, force="ref", task_mask=mk)).lower(
                {"route": i32, "r": i32, "p": i32, "v": i32}, f32, f32,
                jax.ShapeDtypeStruct((), jnp.float32), mask).as_text()
        funcs = dict(re.findall(
            r"func\.func (?:public|private) @([\w.]+)\((.*?)\n  }",
            text, re.S))
        assert "main" in funcs and "c6_tail" in funcs, sorted(funcs)
        assert "scatter" not in text
        # the functions reachable from main without entering c6_tail
        seen, todo = set(), ["main"]
        while todo:
            name = todo.pop()
            if name in seen or name == "c6_tail":
                continue
            seen.add(name)
            todo += re.findall(r"call @([\w.]+)\(", funcs[name])
        op = '"stablehlo.gather"('
        gathers = {name: funcs[name].count(op) for name in seen}
        assert sum(gathers.values()) == 1, gathers
        main = funcs["main"]
        assert 0 <= main.find(op) < main.find("stablehlo.while")


def test_route_windowed_jit_matches_eager_golden():
    """The jitted windowed ``route`` == the original eager composition
    (windowed gate scan -> table-based Stage-1 -> CCG -> temporal
    consistency -> table-based C6), decision-bitwise — with and without
    history."""
    from repro.core.gating import gate_scan_batch
    from repro.core.router import apply_temporal_consistency, route

    m, t = 9, 6
    rng = np.random.default_rng(5)
    gcfg = GateConfig(d_feature=feature_dim())
    gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))
    dx_win = jnp.asarray(rng.normal(size=(m, t, feature_dim())), jnp.float32)
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.75, m), jnp.float32)
    rcfg = RouterConfig()
    histories = [
        (None, None),
        (jnp.asarray(rng.integers(0, 2, m), jnp.int32),
         jnp.asarray(rng.uniform(0, 1, m), jnp.float32)),
    ]
    for prev_route, prev_tau in histories:
        got = route(PROB, gcfg, gparams, dx_win, z, aq,
                    prev_route=prev_route, prev_tau=prev_tau)

        pr = -jnp.ones((m,), jnp.int32) if prev_route is None else prev_route
        pt = jnp.zeros((m,)) if prev_tau is None else prev_tau
        taus_seq, _, _ = gate_scan_batch(gcfg, gparams, dx_win)
        taus = taus_seq[:, -1]
        # table-based Stage-1 (the pre-change implementation)
        f = LAT.accuracy(z)
        f_edge_v1 = f[:, :, -1, 0, 0]
        feasible_edge = f_edge_v1 >= aq[:, None]
        first_ok = jnp.argmax(feasible_edge, axis=1)
        any_ok = feasible_edge.any(axis=1)
        warm_r = jnp.where(any_ok, first_ok, SYS.n_res - 1)
        warm_route = jnp.where(
            any_ok, (taus > rcfg.tau_cloud).astype(jnp.int32), 1)
        warm_route = apply_temporal_consistency(warm_route, pr, taus, pt, rcfg)
        warm_y = LAT.flatten_index(warm_route, warm_r, SYS.n_fps - 1)
        sol = solve_ccg(PROB, z, aq, warm_y=warm_y.astype(jnp.int32))
        sol = dict(sol, route=apply_temporal_consistency(
            sol["route"], pr, taus, pt, rcfg))
        sol, _ = _enforce_bandwidth_table_golden(
            LAT, sol, z, aq, rounds=rcfg.repair_rounds)
        for k in ("route", "r", "p", "v", "iters", "infeasible"):
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(sol[k]), err_msg=k)
        np.testing.assert_allclose(np.asarray(got["tau"]), np.asarray(taus),
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got["warm_route"]),
                                      np.asarray(warm_route))


def test_solve_ccg_finish_is_table_free_identical():
    """The table-free epilogue (bitmask feas + fused best-acc fallback)
    keeps v*/fallback decisions bit-identical to the while_loop oracle on a
    batch mixing converged, warm-started, and all-infeasible lanes."""
    from repro.core.robust import solve_ccg_while

    z = jnp.asarray([0.3, 0.95, 0.6, 0.1, 0.8], jnp.float32)
    aq = jnp.asarray([0.55, 0.99, 0.72, 0.5, 0.99], jnp.float32)  # 1, 4 inf.
    warm_y = jnp.asarray([-1, -1, 12, 0, 3], jnp.int32)
    sol_u = solve_ccg(PROB, z, aq, warm_y=warm_y)
    sol_w = solve_ccg_while(PROB, z, aq, warm_y=warm_y)
    for k in sol_u:
        np.testing.assert_array_equal(
            np.asarray(sol_u[k]), np.asarray(sol_w[k]), err_msg=k)
    assert np.asarray(sol_u["infeasible"]).tolist() == [
        False, True, False, False, True]
