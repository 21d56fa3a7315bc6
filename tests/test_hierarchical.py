"""Hierarchical sharded serving: the O(n_devices) cross-task tail.

Covers the C6 sub-budget algebra (exact budget conservation, headroom
shards untouched, n=1 degeneracy), the hierarchical-vs-dense repair oracle
on slack-carrying solutions (exact C6 satisfaction, per-shard target
satisfaction, per-task demotion gap <= ONE level, feasibility preserved),
1-device bit-identity of the whole sharded run for every policy, the jaxpr
collective audit (no (M,)-sized operand crosses devices inside the
hierarchical round body), the guard rails, and the multi-device subprocess
suites: 8-device decision parity + the measured collective footprint,
churn x outage_collapse x uneven M, and sniper's replicated-profile path.
"""
import dataclasses
import subprocess
import sys as _sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import SystemConfig, accuracy_table
from repro.core.robust import RobustProblem
from repro.core.router import enforce_bandwidth, subbudget_from_stats
from repro.serving.policy import make_policy
from repro.serving.session import ServeSession, _serve_run_sharded
from repro.serving.simulator import SimConfig, Simulator
from repro.sharding.audit import collective_footprint
from repro.sharding.compat import make_mesh

SYS = SystemConfig()
PROB = RobustProblem.build(SYS)
LAT = PROB.lat


# ---------------------------------------------------------------------------
# C6 sub-budget algebra (pure, no mesh)
# ---------------------------------------------------------------------------
def test_subbudget_conserves_exactly():
    """sum(target_d) == min(sum(bw_d), B): the per-shard sub-budgets hand
    out exactly the global C6 budget when it binds and exactly the current
    draw when it does not — no bandwidth is ever lost or invented."""
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 4, 8):
        for _ in range(8):
            bw = jnp.asarray(rng.uniform(0.0, 100.0, n), jnp.float32)
            w = jnp.asarray(rng.integers(1, 9, n), jnp.float32)
            budget = float(rng.uniform(10.0, 500.0))
            t = np.asarray(subbudget_from_stats(bw, w, budget), np.float64)
            want = min(float(np.asarray(bw, np.float64).sum()), budget)
            np.testing.assert_allclose(t.sum(), want, rtol=1e-5)


def test_subbudget_noop_under_budget():
    """With global slack the targets ARE the current draws, bit for bit —
    no shard is asked to demote anything."""
    bw = jnp.asarray([10.0, 25.0, 5.0], jnp.float32)
    w = jnp.asarray([4.0, 4.0, 2.0], jnp.float32)
    t = subbudget_from_stats(bw, w, 100.0)
    np.testing.assert_array_equal(np.asarray(t), np.asarray(bw))


def test_subbudget_only_excess_shards_demote():
    """The whole shortfall lands on shards drawing above their fair share;
    a shard under its fair share keeps its full draw (headroom shards are
    never demoted)."""
    bw = jnp.asarray([10.0, 90.0], jnp.float32)
    w = jnp.asarray([1.0, 1.0], jnp.float32)
    t = np.asarray(subbudget_from_stats(bw, w, 80.0))
    np.testing.assert_allclose(t, [10.0, 70.0], rtol=1e-6)


def test_subbudget_single_shard_degenerates_to_dense():
    """n_devices=1: target == min(bw, B) — the dense repair budget, which
    is what makes the 1-device sharded run bit-identical to dense."""
    for bw, b in ((50.0, 80.0), (120.0, 80.0)):
        t = float(np.asarray(subbudget_from_stats(
            jnp.asarray([bw], jnp.float32), jnp.asarray([7.0], jnp.float32),
            b))[0])
        assert abs(t - min(bw, b)) < 1e-5


# ---------------------------------------------------------------------------
# hierarchical repair vs the dense oracle (slack-carrying solutions)
# ---------------------------------------------------------------------------
def _inflated(m=32, seed=5):
    """Max-fidelity configs with loose requirements: real demotion slack.
    (CCG solutions are cost-minimal, so serve-level repair is a documented
    no-op on them — see test_router.test_enforce_bandwidth_noop_on_ccg...)"""
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


def _hier_repair(sol, z, aq, budget, n_dev, rounds=64):
    """The hierarchical C6 program, spelled as a host loop over shards:
    per-shard draw/weight stats -> scalar sub-budget split -> per-shard
    dense repair against its own target.  Exactly what repair_local runs
    under shard_map, minus the mesh."""
    m = z.shape[0]
    ml = m // n_dev
    bw = np.asarray(LAT.solution_bandwidth(sol))
    bwd = jnp.asarray([bw[d * ml:(d + 1) * ml].sum() for d in range(n_dev)],
                      jnp.float32)
    w = jnp.full((n_dev,), ml, jnp.float32)
    targets = np.asarray(subbudget_from_stats(bwd, w, budget))
    parts = []
    for d in range(n_dev):
        sl = slice(d * ml, (d + 1) * ml)
        sub = {k: v[sl] for k, v in sol.items()}
        fixed, _ = enforce_bandwidth(SYS, sub, z[sl], aq[sl],
                                     total_budget=float(targets[d]),
                                     rounds=rounds)
        parts.append(fixed)
    return {k: jnp.concatenate([p[k] for p in parts]) for k in sol}, targets


def _demotion_depth(sol):
    return ((SYS.n_res - 1 - np.asarray(sol["r"]))
            + (SYS.n_fps - 1 - np.asarray(sol["p"]))
            + (SYS.num_versions - 1 - np.asarray(sol["v"])))


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_hier_repair_exact_c6_and_one_level_gap(n_dev):
    """The tentpole contract, on a binding budget (half the start draw):

    * the hierarchical result meets the GLOBAL C6 budget exactly,
    * every shard meets its own sub-budget,
    * per task the demotion depth differs from the dense oracle by at most
      ONE level,
    * every demoted task stays feasible (accuracy >= aq + robust margin).
    """
    z, aq, sol = _inflated()
    start = float(np.asarray(LAT.solution_bandwidth(sol)).sum())
    budget = 0.5 * start
    dense, _ = enforce_bandwidth(SYS, sol, z, aq, total_budget=budget,
                                 rounds=64)
    dense_bw = float(np.asarray(LAT.solution_bandwidth(dense)).sum())
    assert dense_bw <= budget + 1e-4           # the oracle itself binds
    assert _demotion_depth(dense).sum() > 0    # ... by actually demoting

    hier, targets = _hier_repair(sol, z, aq, budget, n_dev)
    hier_bw = float(np.asarray(LAT.solution_bandwidth(hier)).sum())
    assert hier_bw <= budget + 1e-4            # exact global C6
    assert targets.sum() <= budget + 1e-4      # sub-budgets conserve
    ml = z.shape[0] // n_dev
    for d in range(n_dev):                     # per-shard satisfaction
        sub = {k: v[d * ml:(d + 1) * ml] for k, v in hier.items()}
        sbw = float(np.asarray(LAT.solution_bandwidth(sub)).sum())
        assert sbw <= targets[d] + 1e-4, (d, sbw, targets[d])

    gap = np.abs(_demotion_depth(dense) - _demotion_depth(hier))
    assert gap.max() <= 1, gap

    f = np.asarray(accuracy_table(SYS, z))
    idx = np.arange(z.shape[0])
    acc = f[idx, np.asarray(hier["r"]), np.asarray(hier["p"]),
            np.asarray(hier["v"]), np.asarray(hier["route"])]
    assert np.all(acc >= np.asarray(aq) + SYS.acc_margin_robust - 1e-6)


# ---------------------------------------------------------------------------
# full sharded run: 1-device bit-identity + collective audit + guards
# ---------------------------------------------------------------------------
def _serve_stream(m, r, seed=7, bw_scale=0.45):
    simc = SimConfig(n_tasks=m, n_rounds=r, seed=seed, bw_fluctuation=0.15)
    stream = Simulator(SYS, simc).sample_stream(r)
    if bw_scale is not None:   # make the C6 repair budget bind
        stream = dataclasses.replace(
            stream, bw_scale=jnp.full((r,), bw_scale, jnp.float32))
    return simc, stream


@pytest.mark.parametrize(
    "name", ["r2evid", "rdap", "jcab", "a2_cloud_only", "sniper"])
def test_one_device_hierarchical_bit_identical(name):
    """n_devices=1: the hierarchical tail degenerates to the dense program
    (sub-budget == min(bw, B), partitioned pool == the whole pool) — every
    metric bit-identical for every registered policy, sniper included."""
    simc, stream = _serve_stream(m=12, r=5, seed=3)
    pol = make_policy(name, SYS)
    dense = ServeSession(pol, 12, sim=simc).run(stream)
    mesh = make_mesh((1,), ("data",))
    hier = ServeSession(pol, 12, sim=simc, hierarchical=True).run_sharded(
        mesh, stream)
    assert set(dense) == set(hier)
    for k in dense:
        np.testing.assert_array_equal(np.asarray(dense[k]),
                                      np.asarray(hier[k]), err_msg=k)


def test_round_body_collectives_are_device_count_sized():
    """The structural invariant, measured on the jaxpr: inside the scan
    body the hierarchical mode moves only the (2,)-stat gather and the
    2-int psum across devices, while the gathered oracle moves
    m_local-sized arrays.  One stray all_gather of a per-task array fails
    this test."""
    m = 24
    simc, stream = _serve_stream(m=m, r=3)
    pol = make_policy("r2evid", SYS)
    mesh = make_mesh((1,), ("data",))
    state = pol.init(m)

    def footprint(hier):
        return collective_footprint(
            lambda st, obs: _serve_run_sharded(
                pol, st, obs, simc.n_edge_servers, simc.n_cloud_servers,
                mesh, "data", stream.dx is not None, None, None, None, hier),
            state, stream)

    hier_loop = [s for _, s, in_loop in footprint(True) if in_loop]
    assert hier_loop, "hierarchical round body exchanges no stats at all?"
    assert max(hier_loop) <= 4, hier_loop
    gath_loop = [s for name, s, in_loop in footprint(False)
                 if in_loop and "all_gather" in name]
    assert max(gath_loop) >= m, gath_loop


def test_hierarchical_rejects_hedge():
    """The hedge deadline quantile is a global order statistic — the
    hierarchical mode must refuse it loudly, not approximate it."""
    simc, stream = _serve_stream(m=8, r=2, bw_scale=None)
    sess = ServeSession(make_policy("rdap", SYS), 8, sim=simc,
                        hedge=(0.9, 0.05), hierarchical=True)
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="hedge"):
        sess.run_sharded(mesh, stream)


# ---------------------------------------------------------------------------
# multi-device subprocess suites (device count locks at first jax init)
# ---------------------------------------------------------------------------
def _run_sub(script, timeout=600):
    out = subprocess.run([_sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout, out.stdout[-1000:]


def test_eight_device_decision_parity_and_footprint():
    """8 fake devices, M=64, pools 16/8: the gathered oracle reproduces
    dense on every key; the hierarchical mode reproduces every DECISION
    (route/r/p/v) and the per-task accuracy/energy exactly, keeps delay and
    cost finite (queueing reflects the partitioned pools), bounds the
    in-loop collective footprint at O(n_devices) scalars, and the static
    divisibility guard fires."""
    _run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.sharding.compat import make_mesh
        import numpy as np
        from repro.core.cost_model import SystemConfig
        from repro.serving.policy import make_policy
        from repro.serving.session import ServeSession, _serve_run_sharded
        from repro.serving.simulator import SimConfig, Simulator
        from repro.sharding.audit import max_loop_collective_elems

        sys_ = SystemConfig()
        m, r = 64, 4
        simc = SimConfig(n_tasks=m, n_rounds=r, seed=7, bw_fluctuation=0.2)
        stream = Simulator(sys_, simc).sample_stream(r)
        stream = dataclasses.replace(
            stream, bw_scale=jnp.full((r,), 0.5, jnp.float32))
        pol = make_policy("r2evid", sys_)
        kw = dict(sim=simc, n_edge=16, n_cloud=8)
        dense = ServeSession(pol, m, **kw).run(stream)
        mesh = make_mesh((8,), ("data",))
        gath = ServeSession(pol, m, **kw).run_sharded(mesh, stream)
        hier = ServeSession(pol, m, **kw).run_sharded(
            mesh, stream, hierarchical=True)
        for k in dense:
            np.testing.assert_allclose(
                np.asarray(dense[k]), np.asarray(gath[k]),
                atol=1e-5, rtol=1e-5, err_msg="gathered " + k)
        for k in ("route", "r", "p", "v"):
            np.testing.assert_array_equal(
                np.asarray(dense[k]), np.asarray(hier[k]),
                err_msg="hier " + k)
        for k in ("accuracy", "energy"):
            np.testing.assert_allclose(
                np.asarray(dense[k]), np.asarray(hier[k]),
                atol=1e-5, rtol=1e-5, err_msg="hier " + k)
        for k in ("delay", "cost"):
            v = np.asarray(hier[k])
            assert np.isfinite(v).all(), k
        assert (np.asarray(hier["delay"]) > 0).all()

        state = pol.init(m)
        foot = lambda h: max_loop_collective_elems(
            lambda st, obs: _serve_run_sharded(
                pol, st, obs, 16, 8, mesh, "data", stream.dx is not None,
                None, None, None, h),
            state, stream)
        h, g = foot(True), foot(False)
        assert h <= 4, ("hierarchical round body moved", h, "elems")
        assert g >= m // 8, g

        try:
            ServeSession(pol, m, sim=simc, n_edge=16, n_cloud=9).run_sharded(
                mesh, stream, hierarchical=True)
        except ValueError as e:
            assert "divide" in str(e), e
        else:
            raise AssertionError("indivisible pool accepted")
        print("OK")
        """)


def test_uneven_m_churn_outage_collapse_parity():
    """4 fake devices, M=13 (pads to 16), slot-pool churn composed with the
    outage_collapse scenario: the gathered mode reproduces dense on every
    key; the hierarchical mode keeps the admission arithmetic and every
    decision identical (alive/route/r/p/v exact, accuracy close)."""
    _run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.sharding.compat import make_mesh
        import numpy as np
        from repro.core.cost_model import SystemConfig
        from repro.serving.policy import make_policy
        from repro.serving.scenarios import apply_scenario, compile_scenario
        from repro.serving.session import AdmissionConfig, ServeSession
        from repro.serving.simulator import SimConfig, Simulator

        sys_ = SystemConfig()
        m, r = 13, 8
        simc = SimConfig(n_tasks=m, n_rounds=r, seed=11, bw_fluctuation=0.2,
                         n_edge_servers=8, n_cloud_servers=4)
        stream = Simulator(sys_, simc).sample_stream(r)
        rng = np.random.default_rng(0)
        stream = dataclasses.replace(
            stream,
            arrive_n=jnp.asarray(rng.poisson(2.0, size=r), jnp.int32),
            depart=jnp.asarray(rng.random((r, m)) < 0.15))
        trace = compile_scenario("outage_collapse", sys_, simc, r, seed=0)
        stream = apply_scenario(stream, trace)

        pol = make_policy("r2evid", sys_)
        acfg = AdmissionConfig(init_alive=m // 2)
        dense = ServeSession(pol, m, sim=simc, admission=acfg).run(stream)
        mesh = make_mesh((4,), ("data",))
        gath = ServeSession(pol, m, sim=simc,
                            admission=acfg).run_sharded(mesh, stream)
        hier = ServeSession(pol, m, sim=simc, admission=acfg).run_sharded(
            mesh, stream, hierarchical=True)
        assert set(dense) == set(gath) == set(hier)
        for k in dense:
            np.testing.assert_allclose(
                np.asarray(dense[k]), np.asarray(gath[k]),
                atol=1e-5, rtol=1e-5, err_msg="gathered " + k)
        for k in ("alive", "route", "r", "p", "v",
                  "queue_depth", "admitted", "dropped"):
            np.testing.assert_array_equal(
                np.asarray(dense[k]), np.asarray(hier[k]),
                err_msg="hier " + k)
        np.testing.assert_allclose(
            np.asarray(dense["accuracy"]), np.asarray(hier["accuracy"]),
            atol=1e-5, rtol=1e-5, err_msg="hier accuracy")
        alive = np.asarray(hier["alive"])
        for k in ("cost", "delay", "energy", "accuracy"):
            v = np.asarray(hier[k])
            assert (v[~alive] == 0.0).all() and np.isfinite(v).all(), k
        print("OK")
        """)


def test_sniper_sharded_replicated_profile_parity():
    """4 fake devices: sniper's profile table is kept replicated and
    preseeded once from the gathered round-0 batch — the gathered run
    matches dense bit for bit (decisions) and the hierarchical run keeps
    decisions + accuracy identical (only queueing reflects the
    partitioned pools)."""
    _run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.sharding.compat import make_mesh
        import numpy as np
        from repro.core.cost_model import SystemConfig
        from repro.serving.policy import make_policy
        from repro.serving.session import ServeSession
        from repro.serving.simulator import SimConfig, Simulator

        sys_ = SystemConfig()
        m, r = 12, 6
        simc = SimConfig(n_tasks=m, n_rounds=r, seed=2, bw_fluctuation=0.15,
                         n_edge_servers=8, n_cloud_servers=4)
        stream = Simulator(sys_, simc).sample_stream(r)
        pol = make_policy("sniper", sys_)
        dense = ServeSession(pol, m, sim=simc).run(stream)
        mesh = make_mesh((4,), ("data",))
        gath = ServeSession(pol, m, sim=simc).run_sharded(mesh, stream)
        hier = ServeSession(pol, m, sim=simc).run_sharded(
            mesh, stream, hierarchical=True)
        for k in ("route", "r", "p", "v"):
            np.testing.assert_array_equal(
                np.asarray(dense[k]), np.asarray(gath[k]),
                err_msg="gathered " + k)
            np.testing.assert_array_equal(
                np.asarray(dense[k]), np.asarray(hier[k]),
                err_msg="hier " + k)
        for k in dense:
            np.testing.assert_allclose(
                np.asarray(dense[k]), np.asarray(gath[k]),
                atol=1e-6, rtol=1e-6, err_msg="gathered " + k)
        np.testing.assert_allclose(
            np.asarray(dense["accuracy"]), np.asarray(hier["accuracy"]),
            atol=1e-6, rtol=1e-6, err_msg="hier accuracy")
        print("OK")
        """)
