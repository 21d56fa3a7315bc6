"""Continuous-batching dispatch executor: parity, scheduling invariants,
and the measured-feedback loop into the router.

The serial ``ModelPool.serve_segment`` path is the parity oracle: the
executor's bucketed prefills + token-level slab decode must reproduce its
decoded ids request-for-request, regardless of co-batching, arrival order,
or tier interleave.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.cost_model import SystemConfig
from repro.launch.serve import serve, tier_configs
from repro.serving.dispatch import (
    DispatchExecutor,
    PoolExecutor,
    Request,
    serve_serial_oracle,
)
from repro.serving.policy import Observation, make_policy
from repro.serving.pools import ModelPool, make_tier_pools
from repro.serving.session import AdmissionConfig, ServeSession

SYS = SystemConfig()


class _TickClock:
    """Deterministic clock: each read advances one tick.  Waits and services
    become schedule-step counts, so feedback assertions are exact."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(scope="module")
def pools():
    return make_tier_pools(get_smoke_config("qwen1.5-0.5b"),
                           get_smoke_config("qwen3-8b"))


def _mixed_requests(pools, m=12, seed=0, decode_tokens=6):
    """Mixed-tier, mixed-length request set (prompt lengths 16/32/48 — the
    discrete fidelity sizes the session's dispatch produces)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(m):
        tier = int(rng.integers(0, 2))
        n = 16 * int(rng.integers(1, 4))
        vocab = pools[tier].cfg.vocab_size
        toks = ((i * 131 + np.arange(n)) % vocab).astype(np.int32)
        reqs.append(Request(stream=i, tier=tier, tokens=toks,
                            decode_tokens=decode_tokens))
    return reqs


# ---------------------------------------------------------------------------
# Parity with the serial oracle
# ---------------------------------------------------------------------------
def test_executor_matches_serial_oracle(pools):
    reqs = _mixed_requests(pools, m=12)
    want = serve_serial_oracle(
        pools, [dataclasses.replace(r) for r in reqs])
    ex = DispatchExecutor(pools, n_slots=4, max_prefill_batch=2)
    stats = ex.serve(reqs)
    got = {c.stream: c.ids
           for t in ex.execs for c in ex.execs[t].completions}
    assert set(got) == set(want)
    for s in want:
        np.testing.assert_array_equal(got[s], want[s],
                                      err_msg=f"stream {s} ids diverge")
    # the returned stats cover exactly this request set
    assert sum(st["requests"] for st in stats.values()) == len(reqs)
    toks = sum(st["tokens"] for st in stats.values())
    assert toks == sum(len(r.tokens) + r.decode_tokens for r in reqs)


def test_pools_of_different_slot_counts_co_serve_and_match_serial_oracle(pools):
    """A 16-slot edge slab and a 64-slot cloud slab, set per tier, serve one
    request set together as the serial path does; an int still sets every
    pool, and by default each pool keeps its own count (16)."""
    reqs = _mixed_requests(pools, m=40, seed=3)
    want = serve_serial_oracle(
        pools, [dataclasses.replace(r) for r in reqs])
    ex = DispatchExecutor(pools, n_slots={0: 16, 1: 64}, max_prefill_batch=8)
    assert {t: e.n_slots for t, e in ex.execs.items()} == {0: 16, 1: 64}
    assert ex.execs[1].slab["length"].shape == (64,)
    ex.serve(reqs)
    got = {c.stream: c.ids
           for t in ex.execs for c in ex.execs[t].completions}
    assert set(got) == set(want)
    for s in want:
        np.testing.assert_array_equal(got[s], want[s],
                                      err_msg=f"stream {s} ids diverge")
    assert {t: e.n_slots for t, e in
            DispatchExecutor(pools, n_slots=4).execs.items()} == {0: 4, 1: 4}
    assert {t: e.n_slots for t, e in
            DispatchExecutor(pools).execs.items()} == {0: 16, 1: 16}
    sess = ServeSession(make_policy("r2evid", SYS), 4, pools=pools,
                        n_slots={0: 16, 1: 64})
    assert sess.executor.execs[1].n_slots == 64


def test_join_leave_does_not_perturb_decodes(pools):
    """A segment's decoded ids are independent of which other segments share
    its decode batch: serve one request alone, then co-batched with segments
    that join mid-flight and leave early — identical ids."""
    vocab = pools[0].cfg.vocab_size
    mk = lambda s, n, d: Request(
        stream=s, tier=0,
        tokens=((s * 131 + np.arange(n)) % vocab).astype(np.int32),
        decode_tokens=d)

    alone = DispatchExecutor(pools, n_slots=4)
    alone.serve([mk(0, 32, 10)])
    want = alone.execs[0].completions[0].ids

    ex = DispatchExecutor(pools, n_slots=4, max_prefill_batch=2)
    # short-lived neighbor admitted with stream 0, leaves after 2 decodes
    ex.submit([mk(0, 32, 10), mk(1, 32, 2)])
    for _ in range(4):
        ex.step()
    # late joiner at a different prompt length, different cache depth
    ex.submit([mk(2, 16, 6)])
    ex.drain()
    got = {c.stream: c.ids for c in ex.execs[0].completions}
    np.testing.assert_array_equal(got[0], want)
    # neighbors also match their own solo references
    for s, n, d in ((1, 32, 2), (2, 16, 6)):
        solo = DispatchExecutor(pools, n_slots=4)
        solo.serve([mk(s, n, d)])
        np.testing.assert_array_equal(got[s],
                                      solo.execs[0].completions[0].ids)


# ---------------------------------------------------------------------------
# Scheduling invariants
# ---------------------------------------------------------------------------
def test_queue_drains_and_no_starvation(pools):
    """Every submitted request completes, and the oldest pending request is
    always part of the next admitted prefill bucket (FIFO head defines the
    bucket) — no length class waits unboundedly."""
    reqs = _mixed_requests(pools, m=16, seed=1, decode_tokens=4)
    ex = DispatchExecutor(pools, n_slots=2, max_prefill_batch=2)
    ex.serve(reqs)
    assert ex.idle
    done = {c.stream for t in ex.execs for c in ex.execs[t].completions}
    assert done == {r.stream for r in reqs}
    for t, pex in ex.execs.items():
        for admitted, oldest in pex.admission_log:
            assert oldest in admitted, (
                f"tier {t}: oldest pending stream {oldest} skipped by "
                f"bucket {admitted}")


def test_submit_validates_prompt_length(pools):
    ex = PoolExecutor(pools[0], n_slots=2, max_prefill_len=48)
    with pytest.raises(ValueError, match="prompt length"):
        ex.submit(Request(stream=0, tier=0,
                          tokens=np.zeros((49,), np.int32)))
    with pytest.raises(ValueError, match="prompt length"):
        ex.submit(Request(stream=0, tier=0,
                          tokens=np.zeros((0,), np.int32)))


def test_serve_empty_request_set(pools):
    ex = DispatchExecutor(pools)
    assert ex.serve([]) == {}
    assert ex.idle


def test_serial_path_b0_regression(pools):
    out = pools[0].serve_segment(jnp.zeros((0, 16), jnp.int32),
                                 decode_tokens=4)
    assert out.shape == (0, 4)


# ---------------------------------------------------------------------------
# Stats / measurement
# ---------------------------------------------------------------------------
def test_pool_stats_latency_percentiles(pools):
    pool = ModelPool(get_smoke_config("qwen1.5-0.5b"))
    before = pool.stats.requests
    pool.serve_segment(jnp.ones((3, 16), jnp.int32), decode_tokens=4)
    st = pool.stats
    assert st.requests == before + 3
    assert len(st.latencies) == 3
    assert st.tokens_per_s > 0
    assert 0 < st.p50_s() <= st.p99_s()
    s = st.summary()
    assert {"requests", "tokens", "tokens_per_s", "p50_s", "p99_s"} <= set(s)


def test_dispatch_returns_latency_stats_not_bare_counts(pools):
    ex = DispatchExecutor(pools, n_slots=4, clock=_TickClock())
    stats = ex.serve(_mixed_requests(pools, m=8, seed=2, decode_tokens=4))
    for t, st in stats.items():
        assert st["requests"] > 0
        assert st["tokens_per_s"] > 0
        assert 0 < st["p50_s"] <= st["p99_s"]
        assert st["mean_service_s"] > 0


def test_feedback_loaded_tier_reports_lower_mult(pools):
    """Queueing on one tier shrinks its measured multiplier; an idle tier
    reports 1.0 (no evidence, no adjustment)."""
    clock = _TickClock()
    ex = DispatchExecutor(pools, n_slots=2, max_prefill_batch=2, clock=clock)
    vocab = pools[1].cfg.vocab_size
    reqs = [Request(stream=i, tier=1,
                    tokens=((i * 131 + np.arange(16)) % vocab).astype(np.int32),
                    decode_tokens=4)
            for i in range(12)]
    ex.serve(reqs)
    fb = ex.feedback()
    assert fb["bw_mult"][0] == 1.0           # edge never served: passthrough
    assert fb["bw_mult"][1] < 1.0            # cloud queued: degraded
    assert fb["per_tier"][1]["wait_ewma_s"] > 0
    # reset forgets measurements: feedback returns to passthrough
    ex.reset_measurements()
    fb2 = ex.feedback()
    assert fb2["bw_mult"][1] == 1.0


# ---------------------------------------------------------------------------
# Session integration
# ---------------------------------------------------------------------------
def _session(pools, m, admission=None):
    return ServeSession(make_policy("r2evid", SYS), m, pools=pools,
                        admission=admission)


def test_session_dispatch_sizes_tokens_per_segment(pools):
    """Each routed segment's prompt is sized by ITS OWN fidelity — 16·(1+r_i)
    — not the tier mean the deprecated serial path used."""
    sess = _session(pools, 6)
    sol = {"route": jnp.asarray([0, 0, 1, 1, 1, 0], jnp.int32),
           "r": jnp.asarray([0, 2, 1, 4, 0, 1], jnp.int32),
           "p": jnp.zeros((6,), jnp.int32), "v": jnp.zeros((6,), jnp.int32)}
    sess.dispatch(sol, decode_tokens=2)
    got = {c.stream: c.n_prefill
           for t in sess.executor.execs
           for c in sess.executor.execs[t].completions}
    r = np.asarray(sol["r"])
    assert got == {i: 16 * (1 + int(r[i])) for i in range(6)}


def test_session_dispatch_skips_churned_lanes(pools):
    """Dead slot-pool lanes (route == -1) are never enqueued."""
    sess = _session(pools, 5)
    sol = {"route": jnp.asarray([0, -1, 1, -1, 0], jnp.int32),
           "r": jnp.zeros((5,), jnp.int32),
           "p": jnp.zeros((5,), jnp.int32), "v": jnp.zeros((5,), jnp.int32)}
    sess.dispatch(sol, decode_tokens=2)
    done = {c.stream for t in sess.executor.execs
            for c in sess.executor.execs[t].completions}
    assert done == {0, 2, 4}


def test_session_feedback_changes_routing_decisions(pools):
    """The acceptance loop: a loaded tier's measured feedback, folded into
    the next round's observation via ``apply_feedback``, changes what the
    router decides.  The feedback-scaled ``bw_scale`` shrinks the admission
    budget below the scarcity threshold, so streams admitted under load are
    pinned to minimum fidelity — decisions a feedback-blind session does
    not make."""
    m, rounds = 8, 3
    clock = _TickClock()
    sess = _session(pools, m, admission=AdmissionConfig(init_alive=4))
    sess._executor = DispatchExecutor(
        pools, n_slots=2, max_prefill_batch=2, clock=clock)

    # round 0: serve a routed solution on live pools — cloud heavily loaded,
    # edge lightly (both queue behind the 2-slot slab, cloud much deeper)
    route = np.array([1] * 6 + [0] * 2, np.int32)
    sol = {"route": jnp.asarray(np.tile(route, 3)),
           "r": jnp.ones((3 * m,), jnp.int32),
           "p": jnp.zeros((3 * m,), jnp.int32),
           "v": jnp.zeros((3 * m,), jnp.int32)}
    sess.dispatch(sol, decode_tokens=4)

    fb = sess.feedback()
    assert fb["bw_mult"][1] < 1.0, "loaded cloud tier must report degraded"

    rng = np.random.default_rng(0)
    stream = Observation(
        z=jnp.asarray(rng.uniform(0.4, 0.8, (rounds, m)), jnp.float32),
        aq=jnp.asarray(rng.uniform(0.6, 0.8, (rounds, m)), jnp.float32),
        bw_mult=jnp.ones((rounds, 2), jnp.float32),
        u=jnp.full((rounds, SYS.n_fps - 1), 0.5, jnp.float32),
        arrive_n=jnp.asarray([0, 4, 0], jnp.int32),
        depart=jnp.zeros((rounds, m), bool))

    adjusted = sess.apply_feedback(stream)
    # capacity-weighted scale drops below the admission scarcity threshold
    scale = float(np.asarray(adjusted.bw_scale)[0])
    assert scale < sess.admission.degrade_frac * 1.0, scale
    assert np.all(np.asarray(adjusted.bw_mult)[:, 1] < 1.0)

    base = _session(pools, m, admission=AdmissionConfig(init_alive=4))
    out_blind = base.run(stream)
    sess.reset()
    out_fb = sess.run(adjusted)

    # the 4 streams arriving at round 1 land in slots 4..8; under measured
    # scarcity they are admitted degrade-pinned (r = p = v = 0) while the
    # feedback-blind run serves them at full CCG fidelity
    new = np.s_[1:, 4:]
    assert np.all(np.asarray(out_fb["r"])[new] == 0)
    assert np.any(np.asarray(out_blind["r"])[new] > 0)
    assert not np.array_equal(np.asarray(out_fb["r"]),
                              np.asarray(out_blind["r"]))
    # routing itself stays consistent for the originally alive streams
    np.testing.assert_array_equal(np.asarray(out_fb["alive"]),
                                  np.asarray(out_blind["alive"]))


# ---------------------------------------------------------------------------
# The launcher's serving loop
# ---------------------------------------------------------------------------
def test_launcher_serve_dispatches_every_routed_segment(pools):
    """``launch.serve.serve`` (behind both the launcher and the chip smoke)
    routes each round with ``route_many`` and serves every routed segment of
    the round's last step once, sized by its own fidelity."""
    decode, streams, rounds = 2, 6, 2
    out = serve(pools, streams=streams, rounds=rounds, segments_per_round=2,
                seed=3, requirement="fluctuating", decode_tokens=decode)
    assert len(out["rounds"]) == rounds
    for rnd in out["rounds"]:
        route = np.asarray(rnd["sol"]["route"])
        r = np.asarray(rnd["sol"]["r"])
        assert route.shape == (streams,)
        for tier in pools:
            lanes = route == tier
            st = rnd["served"].get(tier, {"requests": 0, "tokens": 0})
            assert st["requests"] == int(lanes.sum())
            assert st["tokens"] == int((16 * (1 + r[lanes]) + decode).sum())
    assert set(out["pools"]) == {p.name for p in pools.values()}


def test_tier_configs_full_preset_cuts_only_cloud_depth():
    edge, cloud = tier_configs("qwen1.5-0.5b", "qwen3-8b", "full", 5)
    assert edge == get_config("qwen1.5-0.5b")
    whole = get_config("qwen3-8b")
    assert cloud == dataclasses.replace(whole, num_layers=5)
    assert tier_configs("qwen1.5-0.5b", "qwen3-8b", "full")[1] == whole
    assert tier_configs("qwen1.5-0.5b", "qwen3-8b") == (
        get_smoke_config("qwen1.5-0.5b"), get_smoke_config("qwen3-8b"))
    for bad in (0, whole.num_layers + 1):
        with pytest.raises(ValueError, match="cloud_layers"):
            tier_configs("qwen1.5-0.5b", "qwen3-8b", "full", bad)
    with pytest.raises(ValueError, match="preset"):
        tier_configs("qwen1.5-0.5b", "qwen3-8b", "medium")
