"""Unified routing-policy protocol: every router — R2E-VID and all four
baselines — as a registered-pytree bundle with a pure, scan-compatible step.

A :class:`Policy` owns its decision machinery (the shared
:class:`DecisionLattice` / :class:`RobustProblem` tables as pytree data, its
knobs as static metadata) and exposes

    init(n_streams)        -> state          (the per-stream carry pytree)
    decide(state, obs)     -> (state, sol)   (one round; pure jnp)

where ``obs`` is a frozen :class:`Observation` — the per-round observable
bundle (segment motion features, content difficulty, accuracy requirements,
plus the realization inputs the *simulator* consumes; policies never read the
realized ``u``).  Because ``decide`` is pure and the state is a pytree, any
policy runs compiled under ``lax.scan`` / ``shard_map`` — the
:class:`~repro.serving.session.ServeSession` driver gives every policy
batching, carry donation, and stream-axis sharding for free, so baseline
numbers and R2E-VID numbers come from the *same* compiled serve loop.

``decide`` splits into ``decide_stream`` (embarrassingly parallel over
streams — the shardable part) and ``repair`` (the cross-task tail, e.g. the
C6 bandwidth budget; identity for policies without one).  The contract for
sharded serving: ``repair`` may demote per-task fidelity but must not change
anything ``decide_stream``'s returned state depends on (C6 never flips a
route, so the locally-built carry stays exact).

The numpy host closures in :mod:`repro.serving.baselines` are retained as
the decision-for-decision parity oracles (tests/test_policy.py); the ports
here mirror them op for op:

  a2_cloud_only  [Jiang+ RTSS'21]   cloud-pinned nominal argmin
  jcab           [Wang+ INFOCOM'20] mid-ladder nominal, escalate on miss
  rdap           [Su+ 2022]         plans against an EMA difficulty forecast
                                    (the EMA lives in the scan carry)
  sniper         [Liu+ DAC'22]      similarity reuse against a first-round
                                    profile table (the table is the carry)
  r2evid         ours — with gate params: the streaming route_step path
                 (fused gate -> Stage-1 -> warm CCG -> temporal consistency
                 -> C6).  Without gate params: the τ-proxy port of the host
                 method adapter (cold CCG, difficulty-driven consistency).
                 Ablation flags (§4.4) match the host adapter.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.cost_model import SystemConfig, accuracy_at
from repro.core.gating import GateConfig
from repro.core.lattice import DecisionLattice
from repro.core.robust import BIG, RobustProblem, solve_ccg_fused
from repro.core.router import (
    RouterConfig,
    RouterState,
    apply_temporal_consistency,
    clamp_route_available,
    enforce_bandwidth,
    init_router_state,
    route_segment,
    shard_bandwidth_target,
)


# ---------------------------------------------------------------------------
# Observation: the per-round observable bundle
# ---------------------------------------------------------------------------
@partial(
    jax.tree_util.register_dataclass,
    data_fields=("z", "aq", "dx", "bw_mult", "u", "tier_ok", "avail",
                 "lat_mult", "bw_scale", "arrive_n", "depart"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class Observation:
    """What one serving round exposes.  Single-round fields are (M,) /
    (M, d) / (2,) / (K,); a whole run stacks a leading R axis on every field
    and ``ServeSession.run`` scans over it.

    ``dx`` (segment motion features) is optional — policies without a gate
    ignore it.  ``bw_mult`` / ``u`` are *realization* inputs consumed by the
    simulator after the decision; no policy reads the realized ``u`` (the
    paper's information model: methods see ẑ and A^q only).

    The scenario-engine fields (all optional, ``None`` = benign round, the
    pre-scenario program bit-for-bit):

    * ``tier_ok`` (..., 2): per-tier availability the *router* sees —
      health-check knowledge, not adversary state.  An outaged tier is
      infeasible in Stage-1/CCG and clamped away post temporal consistency.
    * ``avail`` (..., S): per-server availability the *realization* sees
      (S = n_edge + n_cloud servers); dead servers take no queue load and
      shrink their tier's uplink share.
    * ``lat_mult`` (..., M, 2): heavy-tailed compute-latency multipliers
      (primary, backup replica) applied at realization; hedged dispatch
      races the backup when the primary blows the deadline quantile.
    * ``bw_scale`` (...,): scenario scale on the C6 bandwidth budget —
      scarcity the repair pass must plan against, distinct from the realized
      ``bw_mult`` fluctuation.

    The churn fields (slot-pool serving — both must be set together, and
    their presence routes ``ServeSession.run`` to the churn driver):

    * ``arrive_n`` (...,): number of new streams asking to join this round
      (Poisson / flash-crowd arrival trace).
    * ``depart`` (..., M): per-slot departure events — a True entry frees
      that slot this round.  Memoryless (geometric-lifetime) draws, so a
      per-(round, slot) Bernoulli trace is exact regardless of when the
      slot was last admitted.
    """
    z: jnp.ndarray                 # (..., M) content difficulty
    aq: jnp.ndarray                # (..., M) accuracy requirements A^q
    dx: Any = None                 # (..., M, d) motion features (gate input)
    bw_mult: Any = None            # (..., 2) per-tier bandwidth fluctuation
    u: Any = None                  # (..., K) realized compute deviation
    tier_ok: Any = None            # (..., 2) per-tier availability (router)
    avail: Any = None              # (..., S) per-server availability (realize)
    lat_mult: Any = None           # (..., M, 2) hedged latency multipliers
    bw_scale: Any = None           # (...,) C6 budget scale
    arrive_n: Any = None           # (...,) stream arrivals (churn)
    depart: Any = None             # (..., M) per-slot departures (churn)

    @property
    def n_streams(self) -> int:
        return self.z.shape[-1]

    @property
    def n_rounds(self) -> int:
        return self.z.shape[0]


def capacity_budget(sys: SystemConfig, tier_ok=None, bw_scale=None):
    """The round's planning bandwidth budget (Mbps) from the scenario's
    capacity telemetry, or ``None`` when no telemetry rides the observation
    (the nominal ``total_bw_mbps`` applies).

    ``bw_scale`` (measured capacity fraction) is the complete statement when
    present; otherwise the binary ``tier_ok`` availability derives the
    surviving tiers' share of the nominal uplink.  Shared by the C6 repair
    (:meth:`R2EVidPolicy.repair`) and the session's admission controller, so
    both plan against the *same* degraded budget.
    """
    if bw_scale is not None:
        return jnp.asarray(sys.total_bw_mbps, jnp.float32) * bw_scale
    if tier_ok is not None:
        cap = sys.edge_bw_mbps + sys.cloud_bw_mbps
        frac = (sys.edge_bw_mbps * (tier_ok[..., 0] > 0)
                + sys.cloud_bw_mbps * (tier_ok[..., 1] > 0)) / cap
        return jnp.asarray(sys.total_bw_mbps, jnp.float32) * frac
    return None


# ---------------------------------------------------------------------------
# Shared vectorized nominal argmin (the jnp port of
# baselines._argmin_feasible — same ops in the same order, so decisions are
# identical to the host oracle bit for bit)
# ---------------------------------------------------------------------------
def _argmin_feasible_jnp(lat: DecisionLattice, z, aq, *, force_route=None,
                         allowed_versions=None, margin=None, tier_ok=None):
    sys = lat.sys
    if margin is None:
        margin = sys.acc_margin_nominal
    f_flat = lat.accuracy_flat(z)                                  # (M, F, K)
    if tier_ok is not None:
        # outaged tiers: infeasible AND out of the max-accuracy fallback
        f_flat = jnp.where(lat.tier_y_ok(tier_ok)[..., None] > 0, f_flat, -BIG)
    total = lat.c1_flat[None, :, None] + lat.b2_flat[None]
    feas = f_flat >= (aq + margin)[:, None, None]
    if force_route is not None:
        y_route, _, _ = lat.unflatten_index(jnp.arange(lat.n_flat))
        feas = feas & (y_route == force_route)[None, :, None]
    if allowed_versions is not None:
        mv = jnp.zeros((sys.num_versions,), bool)
        mv = mv.at[jnp.asarray(allowed_versions)].set(True)
        feas = feas & mv[None, None, :]
    obj = jnp.where(feas, jnp.broadcast_to(total, feas.shape), BIG)
    flat = obj.reshape(obj.shape[0], -1)
    idx = flat.argmin(axis=1)
    # fall back to max-accuracy config when nothing is feasible
    none_ok = jnp.take_along_axis(flat, idx[:, None], axis=1)[:, 0] >= BIG
    best_acc = f_flat.reshape(f_flat.shape[0], -1).argmax(axis=1)
    idx = jnp.where(none_ok, best_acc, idx)
    y = idx // sys.num_versions
    v = idx % sys.num_versions
    route, r, p = lat.unflatten_index(y)
    return {"route": route, "r": r, "p": p, "v": v}


# ---------------------------------------------------------------------------
# Policy protocol
# ---------------------------------------------------------------------------
class Policy:
    """Base protocol.  Subclasses are frozen registered-dataclass pytrees:
    tables (lattice / robust problem / gate params) are data fields, knobs
    are static metadata — so a policy instance passes straight through
    ``jax.jit`` with its config hashed as part of the compilation key."""

    name: str = "policy"
    #: whether ``decide_stream`` is per-task independent (safe to run on a
    #: local stream shard).  Sniper's profile table couples tasks globally
    #: unless its replicated-profile variant preseeds it (the default).
    shardable: bool = True
    #: whether the per-stream carry is identical on every device (global
    #: memory, e.g. sniper's profile table) rather than sharded over
    #: streams.  The sharded session then keeps the state replicated and
    #: calls :meth:`preseed_sharded` once at run start.
    state_replicated: bool = False

    def init(self, n_streams: int):
        """Fresh per-stream carry (any pytree; () for stateless policies)."""
        raise NotImplementedError

    def decide_stream(self, state, obs: Observation):
        """Per-stream portion of the step — no cross-task reductions."""
        raise NotImplementedError

    def repair(self, sol, z, aq, tier_ok=None, bw_scale=None, task_mask=None):
        """Cross-task tail on the full (gathered) batch; identity default.

        ``tier_ok`` / ``bw_scale`` carry the scenario's capacity state so a
        repair pass can plan against the *degraded* budget; ``task_mask`` is
        the slot pool's alive bitmask (dead lanes must not consume budget);
        policies without a repair ignore them.
        """
        return sol

    def repair_local(self, sol, z, aq, *, axis_name, tier_ok=None,
                     bw_scale=None, task_mask=None):
        """Hierarchical cross-task tail on this device's LOCAL stream shard.

        The sharded session's ``hierarchical=True`` mode calls this instead
        of gathering the batch for :meth:`repair`; implementations may only
        exchange O(n_devices) *scalars* over ``axis_name`` (the per-shard
        sub-budget split — see ``docs/SHARDING.md``), never any (M, ...)
        array.  Same contract as ``repair`` otherwise: demote fidelity,
        never flip a route.  Identity default for policies without a tail.
        """
        return sol

    def preseed_sharded(self, state, z, aq, tier_ok=None):
        """One-time run-start hook for replicated-state policies: build the
        global memory (e.g. sniper's first-round profile table) from the
        gathered round-0 ``(z, aq)`` so every device carries the same table
        without any in-scan collective.  Identity default."""
        return state

    def reset_streams(self, state, fresh):
        """Re-initialize the per-stream carry rows where ``fresh`` is True
        (slot reuse under churn): a re-admitted slot is a NEW stream and must
        not inherit the departed stream's gate cell / EMA / history.

        The default resets every state leaf whose leading axis is the stream
        axis row-wise against a fresh ``init``; leaves of any other shape
        (global memory, e.g. sniper's profile table) are left untouched by
        the :class:`SniperPolicy` override.
        """
        m = fresh.shape[0]
        init = self.init(m)

        def pick(i, x):
            if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == m:
                sel = fresh.reshape((m,) + (1,) * (x.ndim - 1))
                return jnp.where(sel, i, x)
            return x

        return jax.tree_util.tree_map(pick, init, state)

    def decide(self, state, obs: Observation):
        """One full round: per-stream decision + cross-task repair."""
        state, sol = self.decide_stream(state, obs)
        return state, self.repair(sol, obs.z, obs.aq, tier_ok=obs.tier_ok,
                                  bw_scale=obs.bw_scale)

    def pad_state(self, state, pad: int):
        """Grow every per-stream leaf by ``pad`` dummy streams (sharding)."""
        from repro.sharding.compat import pad_leading
        return jax.tree_util.tree_map(lambda x: pad_leading(x, pad), state)

    @property
    def lat(self) -> DecisionLattice:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Baselines (paper §4.1.1) as pure jnp policies
# ---------------------------------------------------------------------------
@partial(jax.tree_util.register_dataclass,
         data_fields=("_lat",), meta_fields=())
@dataclasses.dataclass(frozen=True)
class A2CloudOnlyPolicy(Policy):
    """A² — cloud-only joint model-and-data adaptation (stateless)."""
    _lat: DecisionLattice
    name = "a2_cloud_only"

    @property
    def lat(self):
        return self._lat

    def init(self, n_streams):
        return ()

    def decide_stream(self, state, obs):
        return state, _argmin_feasible_jnp(self._lat, obs.z, obs.aq,
                                           force_route=1, tier_ok=obs.tier_ok)


@partial(jax.tree_util.register_dataclass,
         data_fields=("_lat",), meta_fields=())
@dataclasses.dataclass(frozen=True)
class JCABPolicy(Policy):
    """JCAB — nominal single mid-ladder model, escalates version only where
    the mid model misses the requirement (stateless)."""
    _lat: DecisionLattice
    name = "jcab"

    @property
    def lat(self):
        return self._lat

    def init(self, n_streams):
        return ()

    def decide_stream(self, state, obs):
        lat = self._lat
        z, aq = obs.z, obs.aq
        mid = lat.sys.num_versions // 2
        cfg = _argmin_feasible_jnp(lat, z, aq, allowed_versions=[mid],
                                   tier_ok=obs.tier_ok)
        # the host oracle gathers the full accuracy table at the chosen
        # configs; the pointwise formula is bitwise the same check without
        # materializing the (M, N, Z, K, 2) table in the scan body
        ok = accuracy_at(lat.sys, z, cfg["r"], cfg["p"], cfg["v"],
                         cfg["route"]) >= aq
        esc = _argmin_feasible_jnp(lat, z, aq, tier_ok=obs.tier_ok)
        return state, {k: jnp.where(ok, cfg[k], esc[k]) for k in cfg}


class RDAPState(NamedTuple):
    z_ema: jnp.ndarray    # (M,) last observed difficulty (the EMA input)
    has: jnp.ndarray      # (M,) bool — False until the first round lands


@partial(jax.tree_util.register_dataclass,
         data_fields=("_lat",), meta_fields=("ema",))
@dataclasses.dataclass(frozen=True)
class RDAPPolicy(Policy):
    """RDAP — plans against an EMA difficulty forecast ẑ.  The EMA memory is
    the scan carry (the host closure's ``state["z_ema"]`` dict slot)."""
    _lat: DecisionLattice
    ema: float = 0.7
    name = "rdap"

    @property
    def lat(self):
        return self._lat

    def init(self, n_streams):
        return RDAPState(z_ema=jnp.zeros((n_streams,), jnp.float32),
                         has=jnp.zeros((n_streams,), bool))

    def decide_stream(self, state, obs):
        z = obs.z
        # NOTE: plans against the *forecast*; reality realizes obs.z
        z_hat = jnp.where(state.has, self.ema * state.z_ema + (1 - self.ema) * z, z)
        cfg = _argmin_feasible_jnp(self._lat, z_hat, obs.aq,
                                   tier_ok=obs.tier_ok)
        new = RDAPState(z_ema=z.astype(jnp.float32),
                        has=jnp.ones_like(state.has))
        return new, cfg


class SniperState(NamedTuple):
    key: jnp.ndarray      # (n_profiles, 2) profiled (z, aq) keys; +inf = empty
    route: jnp.ndarray    # (n_profiles,) profiled configs
    r: jnp.ndarray
    p: jnp.ndarray
    v: jnp.ndarray
    has: jnp.ndarray      # () bool — profile table captured yet?
    warmup: jnp.ndarray   # () bool — table preseeded at run start: emit the
    #                       per-task fresh configs this round (dense round-0
    #                       semantics), then start the similarity reuse


@partial(jax.tree_util.register_dataclass,
         data_fields=("_lat",), meta_fields=("n_profiles",
                                             "replicated_profile"))
@dataclasses.dataclass(frozen=True)
class SniperPolicy(Policy):
    """Sniper — similarity-aware reuse of the first round's profiled configs.
    The profile table is the carry; it is written exactly once (first round),
    matching the host closure.

    The nearest-profile match is a global cross-task lookup, so under stream
    sharding the table must be REPLICATED, not sharded: with
    ``replicated_profile=True`` (the default) the sharded session keeps the
    state on every device and preseeds the table once at run start from the
    gathered round-0 batch (:meth:`preseed_sharded` + the ``warmup`` flag
    keep round-0 decisions identical to the dense first-round capture).
    ``replicated_profile=False`` restores the historical refusal to run
    sharded at all."""
    _lat: DecisionLattice
    n_profiles: int = 8
    replicated_profile: bool = True
    name = "sniper"

    @property
    def shardable(self):
        return self.replicated_profile

    @property
    def state_replicated(self):
        return True

    @property
    def lat(self):
        return self._lat

    def init(self, n_streams):
        n = self.n_profiles
        return SniperState(
            key=jnp.full((n, 2), jnp.inf, jnp.float32),
            route=jnp.zeros((n,), jnp.int32), r=jnp.zeros((n,), jnp.int32),
            p=jnp.zeros((n,), jnp.int32), v=jnp.zeros((n,), jnp.int32),
            has=jnp.zeros((), bool), warmup=jnp.zeros((), bool),
        )

    def pad_state(self, state, pad):
        # no per-stream leaves: the (n_profiles, ...) table must never grow
        # with the stream padding
        return state

    def reset_streams(self, state, fresh):
        # the profile table is global cross-stream memory, not per-slot
        # state: a newly admitted stream simply matches against the existing
        # profiles (the similarity reuse the policy is built on), so slot
        # reuse resets nothing — and the default's leading-axis heuristic
        # must never touch the (n_profiles, ...) leaves
        return state

    def preseed_sharded(self, state, z, aq, tier_ok=None):
        """Build the round-0 profile table ahead of the scan (the sharded
        run's one-time gather): identical rows to the dense first-round
        capture, with ``warmup`` marking that round 0 must still emit the
        per-task fresh configs rather than table matches."""
        k = min(self.n_profiles, z.shape[0])
        fresh = _argmin_feasible_jnp(self._lat, z[:k], aq[:k],
                                     tier_ok=tier_ok)
        return SniperState(
            key=state.key.at[:k].set(jnp.stack([z[:k], aq[:k]], axis=1)),
            route=state.route.at[:k].set(fresh["route"].astype(jnp.int32)),
            r=state.r.at[:k].set(fresh["r"].astype(jnp.int32)),
            p=state.p.at[:k].set(fresh["p"].astype(jnp.int32)),
            v=state.v.at[:k].set(fresh["v"].astype(jnp.int32)),
            has=jnp.ones((), bool), warmup=jnp.ones((), bool),
        )

    def decide_stream(self, state, obs):
        z, aq = obs.z, obs.aq
        m = z.shape[0]
        n = self.n_profiles
        k = min(n, m)
        fresh = _argmin_feasible_jnp(self._lat, z, aq, tier_ok=obs.tier_ok)
        key = jnp.stack([z, aq], axis=1)                       # (M, 2)
        # reuse most-similar profiled config (the similarity shortcut);
        # +inf keys on unfilled profile rows keep them unreachable
        d = ((key[:, None, :] - state.key[None]) ** 2).sum(-1)  # (M, n)
        nn = d.argmin(axis=1)
        far = d.min(axis=1) > 0.02                       # profile refresh
        reused = {f: jnp.where(far, fresh[f], getattr(state, f)[nn])
                  for f in ("route", "r", "p", "v")}
        # a preseeded table still serves its capture round fresh (warmup)
        use_table = state.has & ~state.warmup
        sol = {f: jnp.where(use_table, reused[f], fresh[f]) for f in reused}
        if obs.tier_ok is not None:
            # a reused profile may point at a tier that has since died
            sol["route"] = clamp_route_available(sol["route"], obs.tier_ok)
        # first-round capture: profile the first k tasks, then freeze
        cap = {f: getattr(state, f).at[:k].set(fresh[f][:k].astype(jnp.int32))
               for f in ("route", "r", "p", "v")}
        new = SniperState(
            key=jnp.where(state.has, state.key,
                          state.key.at[:k].set(key[:k])),
            route=jnp.where(state.has, state.route, cap["route"]),
            r=jnp.where(state.has, state.r, cap["r"]),
            p=jnp.where(state.has, state.p, cap["p"]),
            v=jnp.where(state.has, state.v, cap["v"]),
            has=jnp.ones((), bool), warmup=jnp.zeros((), bool),
        )
        return new, sol


# ---------------------------------------------------------------------------
# R2E-VID
# ---------------------------------------------------------------------------
class HistoryState(NamedTuple):
    """τ-proxy carry: route/score history without a gate recurrence."""
    prev_route: jnp.ndarray   # (M,) int32, -1 = no previous segment
    prev_tau: jnp.ndarray     # (M,) float32


@partial(jax.tree_util.register_dataclass,
         data_fields=("prob", "gate_params"),
         meta_fields=("gate_cfg", "rcfg", "use_gate", "use_stage1",
                      "use_stage2", "force"))
@dataclasses.dataclass(frozen=True)
class R2EVidPolicy(Policy):
    """Ours.  Two operating modes plus the §4.4 ablations:

    * **gate mode** (``gate_params`` given): the streaming engine path —
      fused batched gate over ``obs.dx``, Stage-1, warm-started CCG,
      temporal consistency, C6 repair.  ``decide`` is exactly the
      ``route_step`` computation; the carry is :class:`RouterState`.
    * **τ-proxy mode** (``gate_params=None``): the port of the host method
      adapter — cold CCG + difficulty-driven temporal consistency + C6,
      with (prev_route, prev_z) as the carry.  Decision-identical to the
      retained ``baselines.r2evid`` closure.

    Ablations: ``use_stage1=False`` pins a static mid (r, p) on edge with
    only the robust version choice; ``use_stage2=False`` keeps the adaptive
    config but a fixed mid-ladder version, nominal planning.
    """
    prob: RobustProblem
    gate_params: Any = None
    gate_cfg: GateConfig | None = None
    rcfg: RouterConfig = RouterConfig()
    use_gate: bool = True
    use_stage1: bool = True
    use_stage2: bool = True
    force: str = "auto"
    name = "r2evid"

    def __post_init__(self):
        # gate mode always runs the streaming route_segment path, which
        # bakes the temporal-consistency constraint in — refuse a silently
        # null §4.4 no-gate ablation instead of reporting a wrong effect
        if not self.use_gate and self.gate_params is not None:
            raise ValueError(
                "use_gate=False is the τ-proxy-mode ablation; drop "
                "gate_params to run it")

    @property
    def lat(self):
        return self.prob.lat

    @property
    def _full(self) -> bool:
        return self.use_stage1 and self.use_stage2

    def init(self, n_streams):
        if not self._full:
            return ()
        if self.gate_params is not None:
            return init_router_state(self.gate_cfg, n_streams)
        return HistoryState(
            prev_route=-jnp.ones((n_streams,), jnp.int32),
            prev_tau=jnp.zeros((n_streams,), jnp.float32),
        )

    def pad_state(self, state, pad):
        from repro.sharding.compat import pad_leading
        if not self._full:
            return state
        # dummy streams must carry the no-history marker
        if self.gate_params is not None:
            return RouterState(
                prev_route=pad_leading(state.prev_route, pad, value=-1),
                prev_tau=pad_leading(state.prev_tau, pad),
                gate=jax.tree_util.tree_map(
                    lambda x: pad_leading(x, pad), state.gate),
            )
        return HistoryState(
            prev_route=pad_leading(state.prev_route, pad, value=-1),
            prev_tau=pad_leading(state.prev_tau, pad),
        )

    def decide_stream(self, state, obs):
        lat = self.prob.lat
        sys = lat.sys
        z, aq = obs.z, obs.aq
        if not self.use_stage1:
            # static configuration, no edge-cloud partitioning; robust
            # version choice at the fixed config (worst-case u per v)
            m = z.shape[0]
            fr, fp = sys.n_res // 2, sys.n_fps // 2
            fv = lat.accuracy(z)[:, fr, fp, :, 0]                   # (M, K)
            cost_v = lat.b2[fr, fp, :, 0] * (1.0 + lat.u_dev)       # (K,)
            feas = fv >= aq[:, None]
            v = jnp.where(feas, cost_v[None], BIG).argmin(axis=1)
            v = jnp.where(feas.any(axis=1), v, fv.argmax(axis=1))
            route = jnp.zeros((m,), jnp.int32)
            if obs.tier_ok is not None:
                route = clamp_route_available(route, obs.tier_ok)
            sol = {"route": route,
                   "r": jnp.full((m,), fr, jnp.int32),
                   "p": jnp.full((m,), fp, jnp.int32), "v": v}
            return state, sol
        if not self.use_stage2:
            # adaptive config but single mid model, nominal planning
            return state, _argmin_feasible_jnp(
                lat, z, aq, allowed_versions=[sys.num_versions // 2],
                tier_ok=obs.tier_ok)
        if self.gate_params is not None:
            new_gate, taus, sol = route_segment(
                self.prob, self.gate_cfg, self.gate_params, state,
                obs.dx, z, aq, self.rcfg, force=self.force,
                tier_ok=obs.tier_ok)
            new_state = RouterState(
                prev_route=sol["route"].astype(jnp.int32),
                prev_tau=taus.astype(jnp.float32),
                gate=new_gate,
            )
            return new_state, sol
        # τ-proxy mode: cold CCG, difficulty as the gate-score proxy
        sol = solve_ccg_fused(self.prob, z, aq, force=self.force,
                              tier_ok=obs.tier_ok)
        if self.use_gate:
            taus = z
            route = apply_temporal_consistency(
                sol["route"], state.prev_route, taus, state.prev_tau, self.rcfg)
            if obs.tier_ok is not None:
                route = clamp_route_available(route, obs.tier_ok)
            sol = dict(sol, route=route, tau=taus)
            state = HistoryState(prev_route=route.astype(jnp.int32),
                                 prev_tau=jnp.asarray(taus, jnp.float32))
        return state, sol

    @jax.named_scope("r2e.repair")
    def repair(self, sol, z, aq, tier_ok=None, bw_scale=None, task_mask=None):
        if not self._full:
            return sol
        sys = self.prob.lat.sys
        # plan C6 against the scenario's *degraded* budget: the traced scale
        # (collapse/recovery trace) times the surviving tiers' share of the
        # nominal uplink capacity.  None scenario fields leave total_budget
        # at None — the exact pre-scenario program.  The admission
        # controller derives the same number through capacity_budget, so
        # what C6 plans against is what admission admitted against.
        total_budget = capacity_budget(sys, tier_ok=tier_ok,
                                       bw_scale=bw_scale)
        sol, bw_hist = enforce_bandwidth(self.prob.lat, sol, z, aq,
                                         total_budget=total_budget,
                                         rounds=self.rcfg.repair_rounds,
                                         force=self.force,
                                         task_mask=task_mask)
        # route_step always exposed the repair's bandwidth trajectory;
        # keep it so the RouterEngine shim stays drop-in (the session's
        # serve output filters it out exactly like serve_scan did)
        sol["bw_history"] = bw_hist
        return sol

    def repair_local(self, sol, z, aq, *, axis_name, tier_ok=None,
                     bw_scale=None, task_mask=None):
        """Hierarchical C6: repair this shard against its sub-budget.

        One all-gather of TWO scalars per device — this shard's pre-repair
        bandwidth draw and its alive-lane weight — buys the fleet-wide
        headroom-granted target (:func:`shard_bandwidth_target`); the
        demotion itself then runs entirely shard-locally.  The targets sum
        to ``min(Σbw, B)``, so the composition satisfies C6 exactly
        whenever the dense repair does, and with one device the target is
        ``min(bw, B)`` — the dense program bit for bit.
        """
        if not self._full:
            return sol
        lat = self.prob.lat
        sys = lat.sys
        budget = capacity_budget(sys, tier_ok=tier_ok, bw_scale=bw_scale)
        if budget is None:
            budget = jnp.asarray(sys.total_bw_mbps, jnp.float32)
        bw_i = lat.solution_bandwidth(sol)
        if task_mask is not None:
            bw_i = jnp.where(task_mask, bw_i, 0.0)
            weight = task_mask.sum().astype(jnp.float32)
        else:
            weight = jnp.asarray(bw_i.shape[0], jnp.float32)
        target = shard_bandwidth_target(bw_i.sum(), weight, budget,
                                        axis_name)
        sol, bw_hist = enforce_bandwidth(lat, sol, z, aq,
                                         total_budget=target,
                                         rounds=self.rcfg.repair_rounds,
                                         force=self.force,
                                         task_mask=task_mask)
        sol["bw_history"] = bw_hist
        return sol


# ---------------------------------------------------------------------------
# Registry (the successor of baselines.make_method)
# ---------------------------------------------------------------------------
def _a2(sys: SystemConfig, **kw):
    return A2CloudOnlyPolicy(_lat=DecisionLattice.build(sys), **kw)


def _jcab(sys: SystemConfig, **kw):
    return JCABPolicy(_lat=DecisionLattice.build(sys), **kw)


def _rdap(sys: SystemConfig, **kw):
    return RDAPPolicy(_lat=DecisionLattice.build(sys), **kw)


def _sniper(sys: SystemConfig, **kw):
    return SniperPolicy(_lat=DecisionLattice.build(sys), **kw)


def _r2evid(sys: SystemConfig, **kw):
    return R2EVidPolicy(prob=RobustProblem.build(sys), **kw)


POLICIES = {
    "a2_cloud_only": _a2,
    "jcab": _jcab,
    "rdap": _rdap,
    "sniper": _sniper,
    "r2evid": _r2evid,
}

# the host-closure registry names (baselines.BASELINES) keep working
_ALIASES = {"A2": "a2_cloud_only", "JCAB": "jcab", "RDAP": "rdap",
            "Sniper": "sniper", "R2E-VID": "r2evid"}


def make_policy(name: str, sys: SystemConfig, **kw) -> Policy:
    """Build a registered policy by name (successor of ``make_method``).

    Accepts both the registry names (``a2_cloud_only`` … ``r2evid``) and the
    legacy ``BASELINES`` display names (``A2`` … ``R2E-VID``).
    """
    key = _ALIASES.get(name, name)
    if key not in POLICIES:
        raise KeyError(
            f"unknown policy {name!r}; registered: {sorted(POLICIES)}")
    return POLICIES[key](sys, **kw)
