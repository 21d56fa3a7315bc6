"""ServeSession tests: the unified compiled driver vs the pre-PR-5 goldens
(bit-level shim parity), step-vs-scan identity, the sharded run, the online
gate fine-tune carry, and the deprecation shims."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import SystemConfig
from repro.core.features import feature_dim
from repro.core.gating import GateConfig, gate_specs
from repro.core.robust import RobustProblem
from repro.core.router import RouterEngine, init_router_state, route_scan
from repro.models.params import init_params
from repro.serving.policy import Observation, R2EVidPolicy, make_policy
from repro.serving.scan import serve_scan
from repro.serving.session import FinetuneConfig, ServeSession
from repro.serving.simulator import SimConfig, Simulator
from repro.sharding.compat import make_mesh

SYS = SystemConfig()
PROB = RobustProblem.build(SYS)
GCFG = GateConfig(d_feature=feature_dim())
GPARAMS = init_params(gate_specs(GCFG), jax.random.PRNGKey(0))


def _golden_inputs(m=12, r=6, seed=2026):
    rng = np.random.default_rng(seed)
    dx = jnp.asarray(rng.normal(size=(r, m, feature_dim())), jnp.float32)
    z = jnp.asarray(rng.uniform(0, 1, (r, m)), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.55, 0.82, (r, m)), jnp.float32)
    bwm = jnp.asarray(rng.uniform(0.8, 1.0, (r, 2)), jnp.float32)
    u = jnp.asarray(rng.uniform(0, 0.3, (r, 5)), jnp.float32)
    return dx, z, aq, bwm, u


# captured from the pre-PR-5 serve_scan (PR 4 code) on _golden_inputs():
# the session-based shim must reproduce these decisions exactly and the
# metric row-sums to float32 fidelity.  Re-captured on jax 0.9: its default
# threefry_partitionable PRNG draws different gate parameters from
# PRNGKey(0) (the old values still hold bit for bit with that flag off), and
# the new decisions agree with the looped gate oracle and solve_ccg_while.
GOLD_ROUTE = [[0, 0, 0, 0, 0, 0, 0, 0, 1, 0, 0, 0]] * 6
GOLD_R = [[4, 4, 3, 3, 3, 3, 3, 3, 4, 2, 3, 4],
          [4, 3, 4, 2, 4, 3, 1, 4, 4, 4, 2, 4],
          [4, 3, 3, 4, 4, 4, 2, 3, 3, 3, 4, 4],
          [4, 4, 4, 3, 3, 4, 2, 1, 3, 1, 3, 4],
          [3, 4, 3, 4, 4, 4, 3, 4, 4, 4, 4, 1],
          [1, 4, 4, 4, 3, 4, 4, 4, 3, 3, 3, 2]]
GOLD_V = [[4, 4, 3, 3, 2, 4, 3, 3, 4, 4, 3, 2],
          [4, 4, 4, 4, 4, 4, 4, 4, 4, 2, 3, 1],
          [4, 4, 4, 4, 4, 4, 4, 2, 4, 2, 4, 4],
          [4, 4, 4, 4, 2, 4, 3, 4, 4, 4, 4, 4],
          [4, 3, 4, 2, 4, 4, 3, 4, 4, 4, 1, 3],
          [4, 4, 4, 4, 2, 4, 4, 4, 4, 3, 4, 4]]
GOLD_ROWSUMS = {
    "delay": [16.81609064, 20.77180046, 29.8518275, 21.68784922,
              21.77583945, 21.50788106],
    "energy": [217.6555326, 239.3669922, 138.7282317, 177.6798569,
               220.3759795, 108.1233972],
    "cost": [29.87542218, 35.1338203, 38.17552137, 32.34864056,
             34.9983964, 27.99528491],
    "accuracy": [8.253199637, 8.239819884, 8.563445807, 8.299705267,
                 8.260715365, 8.34043026],
    "tau": [5.736581266, 5.847871095, 5.864398122, 5.681763202,
            5.690836579, 5.853453189],
}
GOLD_FINAL_GATE_H_SUM = -4.95656322222203
GOLD_FINAL_PREV_ROUTE = [0, 0, 0, 0, 0, 0, 0, 0, 1, 0, 0, 0]


def _check_golden(st, mets):
    np.testing.assert_array_equal(np.asarray(mets["route"]), GOLD_ROUTE)
    np.testing.assert_array_equal(np.asarray(mets["r"]), GOLD_R)
    np.testing.assert_array_equal(np.asarray(mets["v"]), GOLD_V)
    for k, want in GOLD_ROWSUMS.items():
        got = np.asarray(mets[k], np.float64).sum(axis=1)
        np.testing.assert_allclose(got, want, rtol=1e-6, err_msg=k)
    if st is not None:
        np.testing.assert_array_equal(np.asarray(st.prev_route),
                                      GOLD_FINAL_PREV_ROUTE)
        np.testing.assert_allclose(
            np.asarray(st.gate.h, np.float64).sum(), GOLD_FINAL_GATE_H_SUM,
            rtol=1e-6)


def test_serve_scan_shim_matches_pr4_golden():
    """The deprecation shim (old signature, session underneath) reproduces
    the PR 4 decisions bit-for-bit and the metrics to float32 fidelity."""
    dx, z, aq, bwm, u = _golden_inputs()
    st, mets = serve_scan(PROB, GCFG, GPARAMS, init_router_state(GCFG, 12),
                          dx, z, aq, bwm, u)
    _check_golden(st, mets)


def test_session_run_matches_pr4_golden_directly():
    """The new-API spelling (policy + session, no shim) hits the same golden."""
    dx, z, aq, bwm, u = _golden_inputs()
    policy = R2EVidPolicy(prob=PROB, gate_params=GPARAMS, gate_cfg=GCFG)
    session = ServeSession(policy, n_streams=12)
    mets = session.run(Observation(z=z, aq=aq, dx=dx, bw_mult=bwm, u=u))
    _check_golden(session.state, mets)


def test_session_step_sequence_matches_run_scan():
    """R ``session.step`` calls == one ``session.run`` scan (carry threading
    and the fused realization agree round for round)."""
    dx, z, aq, bwm, u = _golden_inputs(m=7, r=4)
    policy = R2EVidPolicy(prob=PROB, gate_params=GPARAMS, gate_cfg=GCFG)
    s_run = ServeSession(policy, n_streams=7)
    mets = s_run.run(Observation(z=z, aq=aq, dx=dx, bw_mult=bwm, u=u))
    s_step = ServeSession(policy, n_streams=7)
    for i in range(4):
        out = s_step.step(Observation(z=z[i], aq=aq[i], dx=dx[i],
                                      bw_mult=bwm[i], u=u[i]))
        for k in mets:
            np.testing.assert_allclose(np.asarray(mets[k][i]),
                                       np.asarray(out[k]), atol=1e-6,
                                       err_msg=f"round {i} {k}")
    np.testing.assert_array_equal(np.asarray(s_run.state.prev_route),
                                  np.asarray(s_step.state.prev_route))


@pytest.mark.parametrize("name", ["r2evid", "a2_cloud_only", "jcab", "rdap",
                                  "sniper"])
def test_session_run_sharded_matches_dense(name):
    """On the host mesh the sharded driver agrees with the dense scan for
    every shardable policy (the real multi-shard + padding path is covered
    by tests/test_engine_scan.py's multi-device subprocess through the
    serve_scan shim)."""
    mesh = make_mesh((jax.device_count(),), ("data",))
    scfg = SimConfig(n_rounds=4, n_tasks=6, seed=9, bw_fluctuation=0.1)
    sim = Simulator(SYS, scfg)
    stream = sim.sample_stream(feature_seed=1)
    if name == "r2evid":
        policy = make_policy(name, SYS, gate_cfg=GCFG, gate_params=GPARAMS)
    else:
        policy = make_policy(name, SYS)
    met_a = ServeSession(policy, n_streams=6).run(stream)
    sess_b = ServeSession(policy, n_streams=6)
    met_b = sess_b.run_sharded(mesh, stream)
    assert set(met_a) == set(met_b)
    for k in met_a:
        np.testing.assert_allclose(np.asarray(met_a[k]), np.asarray(met_b[k]),
                                   atol=1e-5, err_msg=k)


def test_session_sharded_rejects_opted_out_sniper():
    """Sniper runs sharded by default via its replicated profile table;
    ``replicated_profile=False`` restores the historical global coupling,
    and the session must refuse to shard THAT rather than silently change
    its decisions."""
    mesh = make_mesh((jax.device_count(),), ("data",))
    sim = Simulator(SYS, SimConfig(n_rounds=2, n_tasks=6, seed=1))
    stream = sim.sample_stream()
    policy = dataclasses.replace(make_policy("sniper", SYS),
                                 replicated_profile=False)
    session = ServeSession(policy, n_streams=6)
    with pytest.raises(ValueError, match="shard"):
        session.run_sharded(mesh, stream)


# ---------------------------------------------------------------------------
# Online gate fine-tuning carry
# ---------------------------------------------------------------------------
def test_finetune_none_is_bit_identical():
    """``finetune=None`` (the default) lowers exactly today's path."""
    dx, z, aq, bwm, u = _golden_inputs()
    policy = R2EVidPolicy(prob=PROB, gate_params=GPARAMS, gate_cfg=GCFG)
    stream = Observation(z=z, aq=aq, dx=dx, bw_mult=bwm, u=u)
    met_a = ServeSession(policy, n_streams=12).run(stream)
    met_b = ServeSession(policy, n_streams=12, finetune=None).run(stream)
    for k in met_a:
        np.testing.assert_array_equal(np.asarray(met_a[k]),
                                      np.asarray(met_b[k]), err_msg=k)
    _check_golden(None, met_b)


def test_finetune_updates_gate_params_on_cadence():
    """With a FinetuneConfig the gate parameters move (every resync_period
    rounds), rounds before the first update are untouched, the run stays
    finite, and the caller's policy object keeps its original buffers."""
    dx, z, aq, bwm, u = _golden_inputs()
    policy = R2EVidPolicy(prob=PROB, gate_params=GPARAMS, gate_cfg=GCFG)
    stream = Observation(z=z, aq=aq, dx=dx, bw_mult=bwm, u=u)
    met_plain = ServeSession(policy, n_streams=12).run(stream)
    session = ServeSession(policy, n_streams=12,
                           finetune=FinetuneConfig(lr=1e-2, resync_period=2))
    met_ft = session.run(stream)
    assert np.isfinite(np.asarray(met_ft["cost"])).all()
    # first update applies after round 2 — rounds 0-1 identical to plain
    for k in met_plain:
        np.testing.assert_array_equal(np.asarray(met_ft[k][:2]),
                                      np.asarray(met_plain[k][:2]), err_msg=k)
    before = jax.tree_util.tree_leaves(policy.gate_params)
    after = jax.tree_util.tree_leaves(session.gate_params)
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(before, after)), "no parameter moved"
    # the donated carry must not have consumed the caller's params
    for a, b in zip(before, jax.tree_util.tree_leaves(GPARAMS)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a second run continues the round counter without recompiling state
    met_ft2 = session.run(stream)
    assert np.isfinite(np.asarray(met_ft2["cost"])).all()
    assert int(session._rounds_done) == 12


def test_finetune_requires_gate_mode():
    with pytest.raises(ValueError, match="gate"):
        ServeSession(make_policy("jcab", SYS), n_streams=4,
                     finetune=FinetuneConfig())


# ---------------------------------------------------------------------------
# RouterEngine deprecation shim
# ---------------------------------------------------------------------------
def test_router_engine_shim_matches_route_scan():
    """engine.step_many (session underneath) == the raw route_scan driver,
    bit for bit, including the threaded carry."""
    m, s = 6, 5
    rng = np.random.default_rng(3)
    z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.7, m), jnp.float32)
    dx_seq = jnp.asarray(rng.normal(size=(s, m, feature_dim())), jnp.float32)
    st, sols_raw = route_scan(PROB, GCFG, GPARAMS, init_router_state(GCFG, m),
                              dx_seq, z, aq)
    engine = RouterEngine(PROB, GCFG, GPARAMS, n_streams=m)
    sols = engine.step_many(dx_seq, z, aq)
    for k in ("route", "r", "p", "v"):
        np.testing.assert_array_equal(np.asarray(sols[k]),
                                      np.asarray(sols_raw[k]), err_msg=k)
    np.testing.assert_allclose(np.asarray(sols["tau"]),
                               np.asarray(sols_raw["tau"]), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(engine.state.prev_route),
                                  np.asarray(st.prev_route))


def test_simulator_run_rejects_host_closures():
    """The method(rnd, state) plumbing is gone — a clear error points at
    make_policy instead of silently doing something different."""
    from repro.serving.baselines import make_method

    sim = Simulator(SYS, SimConfig(n_rounds=2, n_tasks=4))
    with pytest.raises(TypeError, match="make_policy"):
        sim.run(make_method("JCAB", SYS))
