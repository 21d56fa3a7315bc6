"""R2E-VID two-stage router (paper Alg. 1 + Alg. 2 glue) + streaming engine.

Stage 1 (Alg. 1): the temporal gate scores each segment (τ_t); the adaptive
configuration picks the smallest resolution meeting the accuracy requirement
under the *smallest* model (f_i(r, v1) ≥ A^q), escalates to cloud when even
the largest edge config is infeasible, and enforces the temporal-consistency
constraint ‖y_t − y_{t−1}‖₁ ≤ δ(|τ_t − τ_{t−1}|).

Stage 2 (Alg. 2): the CCG robust optimizer refines (r, p, v, y) under the
Γ-budget uncertainty set, warm-started from Stage 1.

The bandwidth budget C6 (Σ B_i ≤ B) is enforced by a vectorized demotion
repair pass: tasks with the most bandwidth and most accuracy slack step down
fidelity until the budget holds.

Two entry points:

  * :func:`route` — windowed, stateless: scans the gate over a whole
    (M, T, d) feature window each call.  Kept for offline planning and
    back-compat.
  * :class:`RouterState` + :func:`route_step` — the streaming engine.  The
    gate hidden state, ring buffer, and previous (route, τ) thread through a
    fully jit-compiled per-segment step, so multi-round serving touches each
    segment's features exactly once and never rebuilds tables.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.cost_model import SystemConfig, accuracy_stage1, fps_norm, res_norm
from repro.core.gating import (
    GateBatchState,
    GateConfig,
    gate_step_batch,
    gate_window_scan,
    init_batch_state,
)
from repro.core.lattice import DecisionLattice
from repro.core.robust import RobustProblem, solve_ccg_fused
from repro.kernels.c6_tail.ops import c6_tail


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    tau_cloud: float = 0.55       # Stage-1 warm-start cloud threshold
    delta0: float = 0.0           # temporal consistency: δ(x) = δ0 + δ1·x
    delta1: float = 4.0
    repair_rounds: int = 8        # C6 demotion passes


def _as_lattice(sys_or_lat) -> DecisionLattice:
    if isinstance(sys_or_lat, DecisionLattice):
        return sys_or_lat
    return DecisionLattice.build(sys_or_lat)


def temporal_flip_allowed(taus, prev_tau, rcfg: RouterConfig):
    """Temporal-consistency constraint (Eq. after (6)): with binary y a route
    FLIP is only allowed when the gate moved enough: δ(|τ_t − τ_{t−1}|) ≥ 1."""
    return (jnp.abs(taus - prev_tau) * rcfg.delta1 + rcfg.delta0) >= 1.0


@jax.named_scope("r2e.consistency")
def apply_temporal_consistency(route, prev_route, taus, prev_tau, rcfg: RouterConfig):
    """Suppress forbidden flips; ``prev_route < 0`` means no history (allowed)."""
    allowed = temporal_flip_allowed(taus, prev_tau, rcfg)
    flip = route != prev_route
    return jnp.where(flip & ~allowed & (prev_route >= 0), prev_route, route)


@jax.named_scope("r2e.consistency")
def clamp_route_available(route, tier_ok):
    """Force routes off outaged tiers.  ``tier_ok``: (..., 2) availability
    (0 = edge, 1 = cloud; <= 0 means down).  Availability overrides every
    other constraint — including temporal consistency — so this runs LAST:
    a stream pinned to a dead tier by its history must still move."""
    route = jnp.where(tier_ok[..., 1] > 0, route, jnp.zeros_like(route))
    route = jnp.where(tier_ok[..., 0] > 0, route, jnp.ones_like(route))
    return route


# ---------------------------------------------------------------------------
# Stage 1: adaptive edge-cloud configuration (Alg. 1)
# ---------------------------------------------------------------------------
@jax.named_scope("r2e.stage1")
def stage1_configure(sys_or_lat, taus, difficulty, acc_req, prev_route, prev_tau,
                     rcfg: RouterConfig = RouterConfig(), tier_ok=None):
    """Vectorized Alg. 1.  All inputs (M,).  Returns route, r_idx warm starts.

    Table-free: the only accuracy values Alg. 1 consults are f_i(r, v1) on
    edge at max fps, so the shared formula is evaluated directly on that
    (M, N) slice (bitwise identical to slicing the broadcast table, which
    this path historically built and threw 99.6% of away).

    ``tier_ok``: optional (2,) tier availability — an outaged tier is never
    selected (the clamp runs after temporal consistency: survivors re-route
    even when their history would pin them to the dead tier).
    """
    sys = sys_or_lat.sys if isinstance(sys_or_lat, DecisionLattice) else sys_or_lat
    # f_i(r, v1) at the max fps, edge tier (Alg.1 line 3: guided by τ)
    f_edge_v1 = accuracy_stage1(sys, difficulty)         # (M, N)
    feasible_edge = f_edge_v1 >= acc_req[:, None]
    # smallest feasible resolution on edge (Alg.1 lines 4-5)
    first_ok = jnp.argmax(feasible_edge, axis=1)
    any_ok = feasible_edge.any(axis=1)
    r_idx = jnp.where(any_ok, first_ok, sys.n_res - 1)
    # Alg.1 line 8: escalate to cloud while infeasible on edge
    route = jnp.where(any_ok, (taus > rcfg.tau_cloud).astype(jnp.int32), 1)
    route = apply_temporal_consistency(route, prev_route, taus, prev_tau, rcfg)
    if tier_ok is not None:
        route = clamp_route_available(route, tier_ok)
    return route, r_idx


# ---------------------------------------------------------------------------
# C6 bandwidth repair
# ---------------------------------------------------------------------------
@jax.named_scope("r2e.repair")
def enforce_bandwidth(sys_or_lat, sol, difficulty, acc_req, total_budget=None,
                      rounds: int = 8, force: str = "auto", task_mask=None):
    """Demote (r, p) of over-budget tasks with the largest bandwidth draw that
    remain feasible after demotion; fixed-round vectorized repair.

    ``task_mask``: optional (M,) bool alive mask (slot-pool churn).  Dead
    lanes contribute zero bandwidth to the budget sum and are never demoted
    (their reclaimable gain is zeroed), so the repair on a masked pool is
    exactly the repair on the compacted alive batch.

    Each round demotes the *top-k* largest-gain tasks at once — exactly the
    prefix (by descending gain) needed to clear the excess over the budget —
    instead of one scalar ``.at[pick].set`` demotion per round, so the repair
    converges in ~#fidelity-levels rounds independent of the batch size M.

    The per-task tail of each round — current draw, candidate-demotion
    accuracies, reclaimable gain — is the fused ``c6_tail`` kernel on the
    hoisted route-indexed (M, N·Z) bandwidth panel (bit-identical to the
    historical ``take_along_axis`` + ``accuracy_at`` body); only the global
    sort/prefix choice stays here.  The scan body holds no data-dependent
    gather or scatter over the M tasks (a TPU runs each as a slow op in
    every pass): the draw is a one-hot fold, the sorted gains come out of
    the sort itself, and the demoted set is a threshold on the sort key.
    Rounds are self-terminating: once a round demotes nothing (or the
    budget holds), every later round is a deterministic no-op on the same
    (r, p), so the scan skips the tail work under a ``lax.cond`` and emits
    the bit-identical ``excess + budget`` history entry.
    """
    lat = _as_lattice(sys_or_lat)
    sys = lat.sys
    budget = sys.total_bw_mbps if total_budget is None else total_budget

    m = sol["r"].shape[0]
    nz = sys.n_fps
    # C6 demotion never flips the route, so the per-task (N, Z) bandwidth
    # panel for its route is round-invariant: hoist the route gather out of
    # the scan body once, flat (r·Z + p)-indexed inside
    bw_panel = jnp.moveaxis(lat.bw, -1, 0)[sol["route"]]   # (M, N, Z)
    bw_panel = bw_panel.reshape(bw_panel.shape[0], -1)     # (M, N·Z)
    # per-pass draw as a one-hot fold over the panel's lanes (as c6_tail
    # folds its own): one nonzero term, so bit-identical to the lane gather.
    # The barrier keeps XLA from merging this lane sum into the draw's total
    # (a reduce of a reduce), which would re-associate the budget sum
    lane = jax.lax.broadcasted_iota(jnp.int32, bw_panel.shape, 1)
    _take_bw = lambda r, p: jax.lax.optimization_barrier(jnp.where(
        lane == (r * nz + p)[:, None], bw_panel, 0.0).sum(axis=1))
    if task_mask is None:
        take_bw = _take_bw
    else:
        take_bw = lambda r, p: jnp.where(task_mask, _take_bw(r, p), 0.0)
    z = jnp.asarray(difficulty, jnp.float32)
    acc_thr = jnp.asarray(acc_req, jnp.float32) + sys.acc_margin_robust
    rn = res_norm(sys)
    pn = fps_norm(sys)
    iota = jax.lax.iota(jnp.int32, m)

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
            # top-k demotion: in descending-gain order, demote tasks while the
            # cumulative reclaimed bandwidth is still short of the excess.
            # One stable sort keyed on -gain carries the index along: the
            # order (ties by index) of jnp.argsort(-gain), and the sorted
            # gains as its negated keys, with no gather to fetch them back
            neg_sorted, order = jax.lax.sort(
                (-gain, iota), num_keys=1, is_stable=True)
            gain_sorted = -neg_sorted
            cum_before = jnp.concatenate(
                [jnp.zeros((1,), gain.dtype), jnp.cumsum(gain_sorted)[:-1]]
            )
            demote_sorted = (cum_before < excess) & (gain_sorted > 0)
            # demote_sorted is a prefix of the sorted order: the positive
            # gains lead it (descending), and over them cum_before only
            # grows, so `cum_before < excess` fails at most once and for
            # good.  Its n members are then the tasks that sort no later
            # than the last one, (g_last, i_last): a threshold test instead
            # of a scatter back to task order.  g_last > 0, so no
            # signed-zero tie arises.
            n = demote_sorted.sum(dtype=jnp.int32)
            last = jnp.maximum(n - 1, 0)
            g_last = jax.lax.dynamic_index_in_dim(gain_sorted, last, keepdims=False)
            i_last = jax.lax.dynamic_index_in_dim(order, last, keepdims=False)
            demote = (n > 0) & ((gain > g_last)
                                | ((gain == g_last) & (iota <= i_last)))
            return (jnp.where(demote & ~can_p, r_dn, r),
                    jnp.where(demote & can_p, p_dn, p),
                    n > 0)

        def skip_round(rp):
            r, p = rp
            return r, p, jnp.asarray(False)

        r, p, progressed = jax.lax.cond(
            active & (excess > 0), demote_round, skip_round, (r, p))
        return (r, p, progressed), excess + budget

    (r, p, _), bw_hist = jax.lax.scan(
        round_fn, (sol["r"], sol["p"], jnp.asarray(True)), None, length=rounds)
    return dict(sol, r=r, p=p), bw_hist


def subbudget_from_stats(bw_d, w_d, budget):
    """Per-shard C6 sub-budgets from the fleet's (draw, weight) stat vectors.

    ``bw_d``: (D,) each shard's pre-repair bandwidth draw; ``w_d``: (D,)
    each shard's alive-lane weight; ``budget``: () the global C6 budget B.
    The fair split is weight-proportional, but a shard under its fair share
    keeps its whole draw (it is never demoted) and *grants* its headroom to
    the over-budget shards, so only the true global shortfall
    ``max(Σbw − B, 0)`` is demoted — pro-rated over the shards that own
    excess:

        fair_d   = B · w_d / Σw
        excess_d = max(bw_d − fair_d, 0);  head_d = max(fair_d − bw_d, 0)
        target_d = bw_d − excess_d · max(Σexcess − Σhead, 0) / Σexcess

    Since Σexcess − Σhead = Σbw − B, the targets sum to ``min(Σbw, B)``:
    repairing each shard to its target meets C6 *exactly* whenever the
    dense repair would, with zero demotion when the budget has slack.
    With one shard this degenerates to ``min(bw, B)`` — the dense budget.
    """
    bw_d = jnp.asarray(bw_d, jnp.float32)
    w_d = jnp.asarray(w_d, jnp.float32)
    fair = budget * w_d / jnp.maximum(w_d.sum(), 1e-9)
    excess = jnp.maximum(bw_d - fair, 0.0)
    head = jnp.maximum(fair - bw_d, 0.0)
    shortfall = jnp.maximum(excess.sum() - head.sum(), 0.0)
    scale = shortfall / jnp.maximum(excess.sum(), 1e-9)
    return bw_d - excess * scale


def shard_bandwidth_target(local_bw, local_weight, budget, axis_name):
    """This shard's C6 repair target from ONE O(n_devices) scalar exchange.

    Inside ``shard_map``: all-gathers the 2-scalar (draw, weight) stat of
    every shard — the only cross-device traffic the hierarchical repair
    needs — and returns this shard's :func:`subbudget_from_stats` entry.
    Demotion then happens entirely within the shards owning the excess.
    """
    stats = jnp.stack([jnp.asarray(local_bw, jnp.float32),
                       jnp.asarray(local_weight, jnp.float32)])
    stats = jax.lax.all_gather(stats, axis_name)            # (D, 2)
    target = subbudget_from_stats(stats[:, 0], stats[:, 1], budget)
    return target[jax.lax.axis_index(axis_name)]


# ---------------------------------------------------------------------------
# Streaming engine: stateful per-segment routing
# ---------------------------------------------------------------------------
@partial(
    jax.tree_util.register_dataclass,
    data_fields=("prev_route", "prev_tau", "gate"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class RouterState:
    """Carry of the streaming router: per-stream gate recurrence + history."""
    prev_route: jnp.ndarray   # (M,) int32, -1 = no previous segment
    prev_tau: jnp.ndarray     # (M,) float32
    gate: GateBatchState      # fused batch: h (M, m), ring buffer + running Σ/Σ²


def init_router_state(gate_cfg: GateConfig, n_streams: int) -> RouterState:
    return RouterState(
        prev_route=-jnp.ones((n_streams,), jnp.int32),
        prev_tau=jnp.zeros((n_streams,), jnp.float32),
        gate=init_batch_state(gate_cfg, n_streams),
    )


def _two_stage_select(
    prob: RobustProblem,
    taus,                 # (M,) gate scores for THIS segment
    difficulty,           # (M,)
    acc_req,              # (M,)
    prev_route,           # (M,)
    prev_tau,             # (M,)
    rcfg: RouterConfig,
    force: str = "auto",
    tier_ok=None,
):
    """Shared Stage-1 → warm-started CCG → temporal-consistency core.

    Both the streaming step (``route_segment``) and the stateless windowed
    ``route`` run exactly this selection once the gate scores are in hand,
    so routing decisions are identical by construction between the two entry
    points.  Returns the pre-C6 solution with tau / warm diagnostics.

    ``tier_ok``: optional (2,) tier availability.  Outaged tiers are
    infeasible inside the CCG (masked encode) and clamped away after the
    temporal-consistency override — availability beats history.
    """
    lat = prob.lat
    warm_route, warm_r = stage1_configure(
        lat, taus, difficulty, acc_req, prev_route, prev_tau, rcfg,
        tier_ok=tier_ok
    )
    # Stage-1 picks (route, r) at max fps — seed CCG with that configuration
    with jax.named_scope("r2e.stage1"):
        warm_y = lat.flatten_index(warm_route, warm_r, lat.sys.n_fps - 1)
    sol = solve_ccg_fused(prob, difficulty, acc_req,
                          warm_y=warm_y.astype(jnp.int32), force=force,
                          tier_ok=tier_ok)
    # Stage-1 consistency overrides Stage-2 route flips that the gate forbids
    route = apply_temporal_consistency(
        sol["route"], prev_route, taus, prev_tau, rcfg
    )
    if tier_ok is not None:
        route = clamp_route_available(route, tier_ok)
    sol = dict(sol, route=route)
    sol["tau"] = taus
    sol["warm_route"] = warm_route
    sol["warm_r"] = warm_r
    return sol


def route_segment(
    prob: RobustProblem,
    gate_cfg: GateConfig,
    gate_params,
    state: RouterState,
    dx,                   # (M, d) motion features of THIS segment per stream
    difficulty,           # (M,)
    acc_req,              # (M,)
    rcfg: RouterConfig = RouterConfig(),
    force: str = "auto",
    tier_ok=None,
):
    """Per-stream portion of the streaming step: gate → Stage-1 → CCG →
    temporal consistency.  Everything here is embarrassingly parallel over
    streams (no cross-task reduction), so the sharded ``serve_scan`` runs it
    on each device's local stream shard; the cross-task C6 repair and
    realization happen after.  Returns ``(new_gate, taus, sol)`` with the
    pre-repair solution (tau / warm diagnostics included).
    """
    new_gate, (taus, _gate_means) = gate_step_batch(
        gate_cfg, gate_params, state.gate, dx, force=force
    )
    sol = _two_stage_select(
        prob, taus, difficulty, acc_req, state.prev_route, state.prev_tau,
        rcfg, force=force, tier_ok=tier_ok
    )
    return new_gate, taus, sol


@partial(jax.jit, static_argnames=("gate_cfg", "rcfg", "force"),
         donate_argnames=("state",))
def route_step(
    prob: RobustProblem,
    gate_cfg: GateConfig,
    gate_params,
    state: RouterState,
    dx,                   # (M, d) motion features of THIS segment per stream
    difficulty,           # (M,)
    acc_req,              # (M,)
    rcfg: RouterConfig = RouterConfig(),
    force: str = "auto",
    tier_ok=None,
):
    """One fully jit-compiled streaming step: (state, segment batch) -> (state, sol).

    Advances the fused batched gate by one segment (O(d) incremental
    volatility, Pallas cell on TPU), runs the two-stage robust selection with
    the Stage-1 configuration seeding the CCG scenario set (true warm start),
    applies the temporal-consistency constraint against the carried history,
    and repairs the C6 bandwidth budget.

    ``state`` is donated: the carry buffers are reused for the new state
    instead of being copied every step, so callers must thread the returned
    state (every in-repo caller already does).
    """
    lat = prob.lat
    new_gate, taus, sol = route_segment(
        prob, gate_cfg, gate_params, state, dx, difficulty, acc_req, rcfg,
        force=force, tier_ok=tier_ok
    )
    sol, bw_hist = enforce_bandwidth(lat, sol, difficulty, acc_req,
                                     rounds=rcfg.repair_rounds, force=force)
    sol["bw_history"] = bw_hist
    new_state = RouterState(
        prev_route=sol["route"].astype(jnp.int32),
        prev_tau=taus.astype(jnp.float32),
        gate=new_gate,
    )
    return new_state, sol


@partial(jax.jit, static_argnames=("gate_cfg", "rcfg"), donate_argnames=("state",))
def route_scan(
    prob: RobustProblem,
    gate_cfg: GateConfig,
    gate_params,
    state: RouterState,
    dx_seq,               # (S, M, d) segment features, scanned over S
    difficulty,           # (M,) or (S, M)
    acc_req,              # (M,) or (S, M)
    rcfg: RouterConfig = RouterConfig(),
):
    """Run ``route_step`` over S segments under one ``lax.scan``.

    The whole multi-segment round compiles to a single program — no Python
    loop, no per-segment dispatch overhead.  Returns ``(state, sols)`` where
    every entry of ``sols`` is stacked with a leading S axis.
    """
    s = dx_seq.shape[0]
    if difficulty.ndim == 1:
        difficulty = jnp.broadcast_to(difficulty, (s,) + difficulty.shape)
    if acc_req.ndim == 1:
        acc_req = jnp.broadcast_to(acc_req, (s,) + acc_req.shape)

    def body(st, xs):
        dx, z, aq = xs
        st, sol = route_step(prob, gate_cfg, gate_params, st, dx, z, aq, rcfg=rcfg)
        return st, sol

    return jax.lax.scan(body, state, (dx_seq, difficulty, acc_req))


class RouterEngine:
    """Deprecation shim: the streaming R2E-VID engine as a thin wrapper over
    :class:`~repro.serving.session.ServeSession` with the gate-mode
    ``r2evid`` policy.

    Kept with the original signature — ``step`` consumes one (M, d) segment
    feature batch and returns the routing solution, ``step_many`` scans S
    segments in one compiled program — and parity-locked bit-for-bit against
    ``route_step`` / ``route_scan`` (the session's decide path lowers the
    exact same computation).  New code should construct a
    :class:`ServeSession` directly.
    """

    def __init__(self, prob: RobustProblem, gate_cfg: GateConfig, gate_params,
                 n_streams: int, rcfg: RouterConfig = RouterConfig()):
        from repro.serving.policy import R2EVidPolicy
        from repro.serving.session import ServeSession

        self.prob = prob
        self.gate_cfg = gate_cfg
        self.gate_params = gate_params
        self.rcfg = rcfg
        self.session = ServeSession(
            R2EVidPolicy(prob=prob, gate_params=gate_params,
                         gate_cfg=gate_cfg, rcfg=rcfg),
            n_streams=n_streams,
        )

    @property
    def state(self) -> RouterState:
        return self.session.state

    @state.setter
    def state(self, value: RouterState):
        self.session.state = value

    def step(self, dx, difficulty, acc_req):
        from repro.serving.policy import Observation
        return self.session.route(Observation(z=difficulty, aq=acc_req, dx=dx))

    def step_many(self, dx_seq, difficulty, acc_req):
        """Consume S segments in one compiled ``lax.scan``.

        dx_seq: (S, M, d).  Returns the stacked solutions; the last entry is
        the current segment's solution.
        """
        return self.session.route_many(dx_seq, difficulty, acc_req)

    def reset(self, n_streams: int | None = None):
        self.session.reset(n_streams)


# ---------------------------------------------------------------------------
# Full two-stage pipeline (windowed / stateless)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("gate_cfg", "rcfg", "force"))
def route(
    prob: RobustProblem,
    gate_cfg: GateConfig,
    gate_params,
    dx_segments,          # (M, T, d) motion features per stream segment window
    difficulty,           # (M,)
    acc_req,              # (M,)
    prev_route=None,      # (M,) previous segment's route (-1 = none)
    prev_tau=None,
    rcfg: RouterConfig = RouterConfig(),
    force: str = "auto",
    tier_ok=None,
):
    """Windowed stateless routing, jit-compiled end to end.

    Scans the fused batched gate step over the (M, T, d) feature window —
    the same ``gate_step_batch`` cell the streaming engine advances, so the
    windowed API shares its kernel dispatch and incremental volatility
    instead of paying the per-stream ``lax.scan`` composition — then runs
    the same ``_two_stage_select`` + C6 repair as the streaming step.
    """
    m = dx_segments.shape[0]
    if prev_route is None:
        prev_route = -jnp.ones((m,), jnp.int32)
    if prev_tau is None:
        prev_tau = jnp.zeros((m,))

    taus_seq, _gates, _ = gate_window_scan(gate_cfg, gate_params, dx_segments,
                                           force=force)
    taus = taus_seq[:, -1]

    sol = _two_stage_select(
        prob, taus, difficulty, acc_req, prev_route, prev_tau, rcfg,
        force=force, tier_ok=tier_ok
    )
    sol, bw_hist = enforce_bandwidth(prob.lat, sol, difficulty, acc_req,
                                     rounds=rcfg.repair_rounds, force=force)
    sol["bw_history"] = bw_hist
    return sol
