"""Slot-pool churn tests: the compiled churn scan vs a host-loop oracle
(bit-identity), none-churn bit-identity with the fixed-M session, alive-lane
parity with a compacted dense run, the admission controller's provable
budget bound under flash-crowd arrivals, masked-lane invariants (no segment
on a dead slot or freed server), the sharded churn path, and the
malformed-failures / empty-batch regression fixes."""
import dataclasses
import subprocess
import sys as _sys
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import SystemConfig
from repro.serving.policy import make_policy
from repro.serving.scenarios import apply_scenario, compile_scenario
from repro.serving.session import (AdmissionConfig, ServeSession,
                                   _churn_round)
from repro.serving.simulator import SimConfig, Simulator

SYS = SystemConfig()
M, R = 16, 10


def _stream(m=M, r=R, seed=5):
    simc = SimConfig(n_tasks=m, n_rounds=r, seed=seed, bw_fluctuation=0.2)
    return simc, Simulator(SYS, simc).sample_stream(r)


def _churn_stream(m=M, r=R, seed=5, churn_seed=0, p_dep=0.15, lam=2.0):
    simc, stream = _stream(m, r, seed)
    rng = np.random.default_rng(churn_seed)
    return simc, dataclasses.replace(
        stream,
        arrive_n=jnp.asarray(rng.poisson(lam, size=r), jnp.int32),
        depart=jnp.asarray(rng.random((r, m)) < p_dep))


# ---------------------------------------------------------------------------
# bit-identity: none-churn == plain fixed-M run
# ---------------------------------------------------------------------------
def test_none_churn_bit_identical_to_plain_run():
    """A full pool with zero arrivals and zero departures must reproduce
    the plain (churn-free) session run bit for bit — the slot-pool carry
    is pure overhead along that path, never a perturbation."""
    simc, stream = _stream()
    nochurn = dataclasses.replace(
        stream, arrive_n=jnp.zeros((R,), jnp.int32),
        depart=jnp.zeros((R, M), bool))
    policy = make_policy("r2evid", SYS)
    plain = ServeSession(policy, M, sim=simc).run(stream)
    churn = ServeSession(policy, M, sim=simc,
                         admission=AdmissionConfig()).run(nochurn)
    assert np.asarray(churn["alive"]).all()
    for k in plain:
        np.testing.assert_array_equal(np.asarray(plain[k]),
                                      np.asarray(churn[k]), err_msg=k)


# ---------------------------------------------------------------------------
# bit-identity: compiled scan == host-loop oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["rdap", "r2evid"])
def test_churn_scan_bit_identical_to_host_loop_oracle(name):
    """The whole churned run is ONE ``lax.scan``; a host loop jitting the
    SAME per-round body (``_churn_round``) round by round must agree bit
    for bit — including which slots are alive, the queue depth, and every
    masked metric."""
    simc, cstream = _churn_stream()
    policy = make_policy(name, SYS)
    acfg = AdmissionConfig(init_alive=M // 2)

    sess = ServeSession(policy, M, sim=simc, admission=acfg)
    mets = sess.run(cstream)

    sys_ = policy.lat.sys
    bw_floor = policy.lat.bw[0, 0, :].max()
    total_bw = jnp.asarray(sys_.total_bw_mbps, jnp.float32)
    valid = jnp.ones((M,), bool)
    step = jax.jit(partial(_churn_round, policy, sys_, bw_floor, total_bw,
                           acfg, simc.n_edge_servers, simc.n_cloud_servers,
                           valid))
    carry = (policy.init(M), jnp.arange(M) < M // 2,
             jnp.zeros((M,), bool), jnp.zeros((), jnp.int32))
    rows = []
    for t in range(R):
        obs_t = jax.tree_util.tree_map(lambda x: x[t], cstream)
        carry, out = step(carry, obs_t)
        rows.append(out)
    for k in mets:
        oracle = np.stack([np.asarray(row[k]) for row in rows])
        np.testing.assert_array_equal(np.asarray(mets[k]), oracle,
                                      err_msg=k)


# ---------------------------------------------------------------------------
# alive-lane parity with a compacted dense run
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["rdap", "r2evid"])
def test_constant_pool_matches_compacted_dense_run(name):
    """With a constant half-full pool (no churn events) the masked-lane
    arithmetic must equal physically removing the dead slots: a dense
    M/2-stream session on the sliced stream reproduces the alive lanes'
    metrics.  This is the oracle for `where(mask, x, 0)` == compaction."""
    k = M // 2
    simc, stream = _stream()
    frozen = dataclasses.replace(
        stream, arrive_n=jnp.zeros((R,), jnp.int32),
        depart=jnp.zeros((R, M), bool))
    policy = make_policy(name, SYS)
    churn = ServeSession(policy, M, sim=simc,
                         admission=AdmissionConfig(init_alive=k)).run(frozen)
    alive = np.asarray(churn["alive"])
    assert (alive == (np.arange(M) < k)[None, :]).all()

    slim = jax.tree_util.tree_map(
        lambda x: x[:, :k] if hasattr(x, "ndim") and x.ndim >= 2
        and x.shape[1] == M else x, stream)
    simc_k = dataclasses.replace(simc, n_tasks=k)
    dense = ServeSession(policy, k, sim=simc_k).run(slim)
    for key in dense:
        np.testing.assert_allclose(
            np.asarray(churn[key])[:, :k], np.asarray(dense[key]),
            atol=1e-6, rtol=1e-6, err_msg=key)
    # the vacant half never realizes anything
    for key in ("cost", "delay", "energy", "accuracy"):
        assert (np.asarray(churn[key])[:, k:] == 0.0).all(), key
    assert (np.asarray(churn["route"])[:, k:] == -1).all()


# ---------------------------------------------------------------------------
# admission controller: the provable budget bound
# ---------------------------------------------------------------------------
def test_admission_respects_budget_under_flash_crowd():
    """Flash-crowd arrivals against a co-timed bandwidth dip: every round
    that admits must leave the pool feasible at minimum fidelity
    (``n_alive * bw_floor <= budget * (1 - margin)``) — zero
    admitted-then-infeasible segments — and the overflow queue stays
    within ``max_queue`` with non-negative drops."""
    simc, stream = _stream()
    trace = compile_scenario("flash_churn", SYS, simc, R, seed=0)
    degraded = apply_scenario(stream, trace)
    policy = make_policy("r2evid", SYS)
    acfg = trace.admission
    mets = ServeSession(policy, M, sim=simc, admission=acfg).run(degraded)

    bw_floor = float(policy.lat.bw[0, 0, :].max())
    budget = float(SYS.total_bw_mbps) * np.asarray(trace.bw_scale)
    alive_n = np.asarray(mets["alive"]).sum(axis=1)
    admitted = np.asarray(mets["admitted"])
    queue = np.asarray(mets["queue_depth"])
    dropped = np.asarray(mets["dropped"])

    adm_rounds = admitted > 0
    assert adm_rounds.any()                      # the crowd does arrive
    assert (alive_n[adm_rounds] * bw_floor
            <= budget[adm_rounds] * (1.0 - acfg.margin) + 1e-4).all(), (
        "admission overflowed the round budget")
    assert (queue <= acfg.max_queue).all()
    assert (dropped >= 0).all()
    assert (queue > 0).any()                     # backpressure was exercised
    # scarcity rounds admit at pinned minimum fidelity only
    scarce = budget < acfg.degrade_frac * float(SYS.total_bw_mbps)
    assert scarce.any()


def test_degrade_pins_hold_minimum_fidelity():
    """A stream admitted while capacity is scarce serves at (r=p=v=0) for
    its whole pool lifetime, even after bandwidth recovers."""
    simc, stream = _stream()
    r0 = 3
    bw = np.ones((R,), np.float32)
    bw[r0:r0 + 2] = 0.3                          # scarcity window
    arrive = np.zeros((R,), np.int32)
    arrive[r0] = 4                               # admitted under scarcity
    degraded = dataclasses.replace(
        stream,
        bw_scale=jnp.asarray(bw),
        arrive_n=jnp.asarray(arrive),
        depart=jnp.zeros((R, M), bool))
    k = M - 6
    mets = ServeSession(
        make_policy("rdap", SYS), M, sim=simc,
        admission=AdmissionConfig(init_alive=k)).run(degraded)
    alive = np.asarray(mets["alive"])
    # the burst landed (scarce budget still fits a few min-fidelity lanes)
    newly = alive[r0] & ~alive[r0 - 1]
    assert newly.any()
    for key in ("r", "p", "v"):
        vals = np.asarray(mets[key])[r0:, newly]
        assert (vals == 0).all(), f"{key} escaped the degrade pin"


# ---------------------------------------------------------------------------
# masked-lane invariants: dead slots and freed servers
# ---------------------------------------------------------------------------
def test_no_segment_lands_on_dead_slot_or_downed_tier():
    """Churn composed with an edge outage: dead slots never realize
    (route=-1, zero metrics) and no *alive* lane routes to the outaged
    tier while its quorum gate is down."""
    simc, cstream = _churn_stream()
    trace = compile_scenario("edge_outage", SYS, simc, R, seed=0)
    degraded = apply_scenario(cstream, trace)
    mets = ServeSession(
        make_policy("r2evid", SYS), M, sim=simc,
        admission=AdmissionConfig(init_alive=M // 2)).run(degraded)
    alive = np.asarray(mets["alive"])
    route = np.asarray(mets["route"])
    assert (route[~alive] == -1).all()
    for key in ("cost", "delay", "energy", "accuracy"):
        vals = np.asarray(mets[key])
        assert (vals[~alive] == 0.0).all(), key
        assert np.isfinite(vals).all(), key
    edge_down = np.asarray(trace.tier_ok)[:, 0] == 0.0
    assert edge_down.any()
    assert (route[edge_down] != 0).all(), \
        "a segment landed on the outaged edge tier"


# ---------------------------------------------------------------------------
# sharded churn path
# ---------------------------------------------------------------------------
def test_sharded_churn_matches_dense():
    """4 fake host devices: the sharded churn scan (replicated admission,
    locally-sliced slot resets) agrees with the dense churn run
    (subprocess: device count locks at first jax init)."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.sharding.compat import make_mesh
        import numpy as np
        from repro.core.cost_model import SystemConfig
        from repro.serving.policy import make_policy
        from repro.serving.session import AdmissionConfig, ServeSession
        from repro.serving.simulator import SimConfig, Simulator

        sys_ = SystemConfig()
        m, r = 16, 8
        simc = SimConfig(n_tasks=m, n_rounds=r, seed=11, bw_fluctuation=0.2)
        stream = Simulator(sys_, simc).sample_stream(r)
        rng = np.random.default_rng(0)
        stream = dataclasses.replace(
            stream,
            arrive_n=jnp.asarray(rng.poisson(2.0, size=r), jnp.int32),
            depart=jnp.asarray(rng.random((r, m)) < 0.15))

        acfg = AdmissionConfig(init_alive=m // 2)
        pol = make_policy("rdap", sys_)
        dense = ServeSession(pol, m, sim=simc, admission=acfg).run(stream)
        mesh = make_mesh((4,), ("data",))
        sess = ServeSession(pol, m, sim=simc, admission=acfg)
        shard = sess.run_sharded(mesh, stream)
        assert set(dense) == set(shard)
        for k in dense:
            np.testing.assert_allclose(
                np.asarray(dense[k]), np.asarray(shard[k]),
                atol=1e-5, rtol=1e-5, err_msg=k)
        print("OK")
        """
    )
    out = subprocess.run([_sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------
def test_churn_requires_admission_config_and_both_traces():
    simc, cstream = _churn_stream()
    sess = ServeSession(make_policy("rdap", SYS), M, sim=simc)
    with pytest.raises(ValueError, match="AdmissionConfig"):
        sess.run(cstream)
    half = dataclasses.replace(cstream, depart=None)
    sess2 = ServeSession(make_policy("rdap", SYS), M, sim=simc,
                         admission=AdmissionConfig())
    with pytest.raises(ValueError, match="BOTH"):
        sess2.run(half)


def test_churn_rejects_hedge():
    simc, cstream = _churn_stream()
    sess = ServeSession(make_policy("rdap", SYS), M, sim=simc,
                        admission=AdmissionConfig(), hedge=(0.9, 0.05))
    with pytest.raises(ValueError, match="hedge"):
        sess.run(cstream)


# ---------------------------------------------------------------------------
# regression: malformed failure plans must raise, not shrink the experiment
# ---------------------------------------------------------------------------
def test_run_elastic_rejects_malformed_failures():
    simc, stream = _stream()
    sess = ServeSession(make_policy("r2evid", SYS), M, sim=simc)
    with pytest.raises(ValueError, match="round 0"):
        sess.run_elastic(stream, {0: [1]})
    with pytest.raises(ValueError, match=f"1..{R - 1}"):
        sess.run_elastic(stream, {R: [1]})
    with pytest.raises(ValueError, match="unknown node 99"):
        sess.run_elastic(stream, {2: [99]}, n_nodes=4)


# ---------------------------------------------------------------------------
# regression: an empty routed batch is a no-op, not a crash
# ---------------------------------------------------------------------------
def test_model_pool_serves_empty_batch():
    from repro.configs import get_smoke_config
    from repro.serving.pools import ModelPool

    pool = ModelPool(get_smoke_config("qwen1.5-0.5b"),
                     jax.random.PRNGKey(0), name="edge")
    out = pool.serve_segment(jnp.zeros((0, 16), jnp.int32), decode_tokens=4)
    assert out.shape == (0, 4) and out.dtype == jnp.int32
    assert pool.stats.requests == 0 and pool.stats.tokens == 0
