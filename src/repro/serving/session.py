"""ServeSession: one compiled, shardable serve driver for every policy.

The session is the single owner of the serving configuration bundle — the
:class:`SystemConfig` / :class:`GateConfig` / :class:`RouterConfig` arrive
inside the :class:`~repro.serving.policy.Policy`, the :class:`SimConfig`
(server pool sizes) and the mesh + stream padding live here — plus the kernel
``force=`` pins and the carry donation discipline.  Every registered policy
(R2E-VID and all four baselines) runs through the same three entry points:

  ``session.step(obs)``          one round (decide, and realize when the
                                 observation carries ``bw_mult``/``u``)
  ``session.run(stream)``        R rounds under ONE ``lax.scan`` with the
                                 realization fused into the scan body;
                                 per-round (R, M) metrics out
  ``session.run_sharded(mesh, stream)``
                                 the same run as ONE compiled *sharded*
                                 scan: the policy's per-stream stage runs on
                                 each device's local stream shard, the
                                 cross-task tail (``Policy.repair`` + LPT
                                 realization) on the all-gathered real-M
                                 batch — metrics identical to the dense path

``session.route(obs)`` / ``session.route_many(...)`` are the decide-only
fast paths backing the :class:`RouterEngine` deprecation shim.  The carry is
donated in every compiled driver (buffers reused, never copied per step) and
threaded through ``self.state``, so callers never handle donation manually.

Optional online gate fine-tuning (``finetune=FinetuneConfig``): the scan
carry additionally threads the gate parameters, and every ``resync_period``
rounds a realized-success gradient step (BCE of the gate scores τ against
the round's SLA misses, proximally anchored at the offline parameters —
paper §3.2's online adaptation driven by what actually happened) updates
them inside the compiled run.  ``finetune=None`` (the default) lowers the
exact same program as before — bit-identical, covered by
tests/test_session.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.gating import gate_step_batch
from repro.runtime.spans import span
from repro.serving.policy import Observation, Policy, capacity_budget
from repro.serving.simulator import SimConfig, realize_rounds

_MET_KEYS = ("delay", "energy", "cost", "accuracy")
_SOL_KEYS = ("route", "r", "p", "v", "tau")


@dataclasses.dataclass(frozen=True)
class FinetuneConfig:
    """Online gate fine-tuning knobs (off unless passed to the session)."""
    lr: float = 1e-3
    resync_period: int = 4     # apply one gradient step every this many rounds
    mu: float = 0.1            # proximal anchor weight (catastrophic-forgetting guard)


@dataclasses.dataclass(frozen=True)
class AdmissionConfig:
    """SLA-aware admission control for slot-pool (churn) runs.

    The controller runs inside the serve scan each round, *before* the
    policies decide: it admits new streams only while every admitted stream
    could still be served at minimum fidelity within the round's bandwidth
    budget (``capacity_budget`` — the same number the C6 repair plans
    against, tightened by ``bw_scale`` / ``tier_ok`` telemetry), queues the
    overflow up to ``max_queue``, and drops the rest.  Streams admitted
    while the budget is below ``degrade_frac`` of nominal are pinned to
    minimum fidelity (r = p = v = 0) for their lifetime in the pool.
    Static — part of the compilation key.
    """
    max_queue: int = 64        # waiting arrivals carried in the scan carry
    margin: float = 0.05       # headroom fraction held back from the budget
    degrade_frac: float = 0.5  # budget/nominal below this => degrade mode
    init_alive: int | None = None   # slots occupied at round 0 (None = all)


def _churn_admit(alive, degr, queue, arrive_n, depart, budget, total_bw,
                 bw_floor, acfg: AdmissionConfig, valid):
    """One round of slot-pool bookkeeping + admission (pure jnp, in-scan).

    Departures free their slots first; then up to ``cap - n_alive`` of the
    waiting streams (``queue`` + this round's ``arrive_n``) are admitted
    into the lowest-indexed free slots, where ``cap`` is the largest pool
    size whose worst-case minimum-fidelity bandwidth (``bw_floor`` per
    stream) fits the round's budget less the safety margin.  That bound is
    the provable SLA statement: admission never creates a stream the C6
    repair cannot fit — zero admitted-then-infeasible segments.

    ``valid`` masks the physically usable slots (all-true on the dense
    path; excludes the sharding pad lanes on the sharded path).  Returns
    ``(alive, degr, queue, newly, admitted, dropped)``.
    """
    alive = alive & ~depart & valid
    n_alive = alive.sum()
    cap = jnp.floor(budget * (1.0 - acfg.margin) / bw_floor).astype(jnp.int32)
    cap = jnp.clip(cap, 0, valid.sum())
    free = valid & ~alive
    want = queue + arrive_n
    can = jnp.clip(cap - n_alive, 0, free.sum())
    admitted = jnp.minimum(want, can)
    backlog = want - admitted
    queue = jnp.minimum(backlog, acfg.max_queue)
    dropped = backlog - queue
    rank = jnp.cumsum(free.astype(jnp.int32))      # 1-indexed among free slots
    newly = free & (rank <= admitted)
    scarce = budget < acfg.degrade_frac * total_bw
    # a freed slot sheds its degrade pin BEFORE re-admission, so a slot
    # reused in the same round starts from the new stream's budget state
    degr = (degr & alive) | (newly & scarce)
    alive = alive | newly
    return alive, degr, queue, newly, admitted, dropped


def _round_output(sol, met):
    """The per-round scan output: deterministic metrics + the decisions."""
    out = {k: met[k] for k in _MET_KEYS}
    out.update({k: sol[k] for k in _SOL_KEYS if k in sol})
    return out


# ---------------------------------------------------------------------------
# Compiled drivers (module-level so the jit cache is shared across sessions;
# the policy's static metadata is part of the compilation key via its pytree
# treedef, its tables are traced operands)
# ---------------------------------------------------------------------------
@partial(jax.jit, donate_argnames=("state",))
def _decide_step(policy, state, obs):
    return policy.decide(state, obs)


@partial(jax.jit, donate_argnames=("state",))
def _decide_scan(policy, state, obs_seq):
    def body(st, obs):
        return policy.decide(st, obs)

    return jax.lax.scan(body, state, obs_seq)


def _realize_obs(sys, obs, sol, n_edge, n_cloud, hedge, task_mask=None,
                 n_tier=None, tier_frac=None):
    """The one realization call every serve driver shares: scenario fault
    inputs (per-server availability, hedged latency draws) ride on the
    observation; ``None`` fields lower the exact pre-scenario program.
    ``n_tier`` / ``tier_frac`` are the hierarchical sharded path's globally
    exchanged fair-share scalars (partitioned server pools)."""
    return realize_rounds(
        sys, obs.z, obs.bw_mult, obs.u, sol["route"], sol["r"], sol["p"],
        sol["v"], n_edge=n_edge, n_cloud=n_cloud,
        avail=obs.avail, lat_mult=obs.lat_mult, hedge=hedge,
        task_mask=task_mask, n_tier=n_tier, tier_frac=tier_frac,
    )


@partial(jax.jit, static_argnames=("n_edge", "n_cloud", "hedge"),
         donate_argnames=("state",))
def _serve_step(policy, state, obs, n_edge, n_cloud, hedge=None):
    sys = policy.lat.sys
    state, sol = policy.decide(state, obs)
    met = _realize_obs(sys, obs, sol, n_edge, n_cloud, hedge)
    return state, _round_output(sol, met)


@partial(jax.jit, static_argnames=("n_edge", "n_cloud", "hedge"),
         donate_argnames=("state",))
def _serve_run(policy, state, obs_seq, n_edge, n_cloud, hedge=None):
    sys = policy.lat.sys

    def body(st, obs):
        st, sol = policy.decide(st, obs)
        met = _realize_obs(sys, obs, sol, n_edge, n_cloud, hedge)
        return st, _round_output(sol, met)

    return jax.lax.scan(body, state, obs_seq)


def _churn_round(policy, sys, bw_floor, total_bw, acfg, n_edge, n_cloud,
                 valid, carry, obs):
    """One slot-pool serving round: admission -> state reset on slot reuse
    -> per-stream decision -> degrade clamp -> masked repair -> masked
    realization.  Shared verbatim by the compiled scan body
    (``_serve_run_churn``) and the host-loop oracle in tests, so the
    bit-identity assertion compares the same per-round program."""
    st, alive, degr, queue = carry
    budget = capacity_budget(sys, tier_ok=obs.tier_ok, bw_scale=obs.bw_scale)
    budget = total_bw if budget is None else budget
    alive, degr, queue, newly, admitted, dropped = _churn_admit(
        alive, degr, queue, obs.arrive_n, obs.depart, budget, total_bw,
        bw_floor, acfg, valid)
    st = policy.reset_streams(st, newly)
    st, sol = policy.decide_stream(st, obs)
    # streams admitted under scarcity serve at minimum fidelity for their
    # pool lifetime (the admission contract their cap was computed against)
    sol = dict(sol, **{k: jnp.where(degr, jnp.zeros_like(sol[k]), sol[k])
                       for k in ("r", "p", "v")})
    sol = policy.repair(sol, obs.z, obs.aq, tier_ok=obs.tier_ok,
                        bw_scale=obs.bw_scale, task_mask=alive)
    met = _realize_obs(sys, obs, sol, n_edge, n_cloud, None, task_mask=alive)
    out = _round_output(sol, met)
    out["route"] = met["route"]        # masked: -1 marks the dead slots
    out.update(alive=alive, queue_depth=queue, admitted=admitted,
               dropped=dropped)
    return (st, alive, degr, queue), out


@partial(jax.jit, static_argnames=("acfg", "n_edge", "n_cloud"),
         donate_argnames=("carry",))
def _serve_run_churn(policy, carry, obs_seq, acfg, n_edge, n_cloud):
    """``_serve_run`` on a fixed-capacity slot pool: the carry additionally
    threads the alive bitmask, the per-slot degrade pins, and the admission
    queue depth; the arrival/departure traces ride the round-stacked
    observation (``arrive_n`` / ``depart``) exactly like the scenario
    fields, so the whole churned run is still ONE ``lax.scan``."""
    sys = policy.lat.sys
    # the per-stream minimum-fidelity bandwidth bound the admission cap is
    # computed against: the worst tier's (r=0, p=0) draw
    bw_floor = policy.lat.bw[0, 0, :].max()
    total_bw = jnp.asarray(sys.total_bw_mbps, jnp.float32)
    valid = jnp.ones_like(carry[1])

    def body(c, obs):
        return _churn_round(policy, sys, bw_floor, total_bw, acfg, n_edge,
                            n_cloud, valid, c, obs)

    return jax.lax.scan(body, carry, obs_seq)


@partial(jax.jit, static_argnames=("ft", "n_edge", "n_cloud", "hedge"),
         donate_argnames=("carry",))
def _serve_run_finetune(policy, carry, obs_seq, anchor, ft, n_edge, n_cloud,
                        hedge=None):
    """``_serve_run`` with the gate parameters threaded through the carry.

    carry = (policy state, gate params, round index).  Every
    ``ft.resync_period`` rounds one SGD step minimizes the realized-success
    BCE: τ should open (offload) exactly where this round's deterministic
    accuracy missed the requirement.  The gradient is truncated to the
    current round's gate cell (the carried recurrence is stop-gradiented),
    and a proximal term μ/2·‖θ − θ_offline‖² anchors against forgetting.
    """
    sys = policy.lat.sys
    gcfg = policy.gate_cfg

    def body(c, obs):
        st, params, i = c
        pol = dataclasses.replace(policy, gate_params=params)
        new_st, sol = pol.decide(st, obs)
        met = _realize_obs(sys, obs, sol, n_edge, n_cloud, hedge)
        fail = (met["accuracy"] < obs.aq).astype(jnp.float32)   # SLA misses

        def loss_fn(p):
            frozen = jax.tree_util.tree_map(jax.lax.stop_gradient, st.gate)
            # force="ref": the jnp cell is the differentiable twin of the
            # Pallas gate_cell (value parity is kernel-tested); the kernel
            # has no VJP, so auto-dispatch would fail under grad on TPU
            _, (taus, _) = gate_step_batch(gcfg, p, frozen, obs.dx,
                                           force="ref")
            eps = 1e-6
            bce = -(fail * jnp.log(taus + eps)
                    + (1.0 - fail) * jnp.log(1.0 - taus + eps)).mean()
            prox = sum(
                jnp.sum(jnp.square(a - b))
                for a, b in zip(jax.tree_util.tree_leaves(p),
                                jax.tree_util.tree_leaves(anchor))
            )
            return bce + 0.5 * ft.mu * prox

        params = jax.lax.cond(
            (i + 1) % ft.resync_period == 0,
            lambda p: jax.tree_util.tree_map(
                lambda a, g: a - ft.lr * g, p, jax.grad(loss_fn)(p)),
            lambda p: p,
            params,
        )
        return (new_st, params, i + 1), _round_output(sol, met)

    return jax.lax.scan(body, carry, obs_seq)


@partial(jax.jit, static_argnames=("n_edge", "n_cloud", "mesh", "mesh_axis",
                                   "has_dx", "hedge", "acfg", "hierarchical"))
def _serve_run_sharded(policy, state, obs_seq, n_edge, n_cloud, mesh,
                       mesh_axis, has_dx, hedge=None, churn=None, acfg=None,
                       hierarchical=False):
    """One compiled sharded scan over the whole run, for ANY shardable policy.

    The policy's per-stream stage (``decide_stream``) runs on each device's
    local shard of the stream axis M (padded to a multiple of the device
    count with dummy streams that the policy's ``pad_state`` marks inert).
    The cross-task tail then runs in one of two modes:

    * **gathered** (``hierarchical=False``, the parity oracle): the
      decisions are all-gathered so ``Policy.repair`` + LPT realization run
      on the exact real-M batch — replicated arithmetic, hence metrics
      identical to the dense path, at the cost of one O(M) collective per
      round.
    * **hierarchical** (``hierarchical=True``): NO (M, ...) array ever
      crosses devices inside the round body.  ``Policy.repair_local``
      repairs each shard against its scalar-exchanged C6 sub-budget
      (:func:`repro.core.router.shard_bandwidth_target`), and realization
      packs each shard's segments onto a statically partitioned slice of
      the server pool, with only the per-shard tier task counts (psum of 2
      ints) and the tier alive fractions exchanged for the uplink
      fair-share terms.  C6 is met exactly; queueing delay reflects the
      partitioned pools (see docs/SHARDING.md for the contract and bound).
      Requires ``n_edge`` / ``n_cloud`` divisible by the device count;
      incompatible with ``hedge`` (the deadline quantile is a global order
      statistic).

    Either way the carry stays local: the repair is contractually forbidden
    from changing anything the per-stream state depends on (C6 demotes
    fidelity, never flips routes), so the locally-built state is already
    exact.  Replicated-state policies (sniper's profile table) instead keep
    their carry whole on every device and are preseeded once at run start
    from the gathered round-0 batch (``Policy.preseed_sharded``) — the one
    O(M) gather those policies need, outside the scan.

    ``churn`` (optional): the slot pool's ``(alive, degr, queue)`` carry at
    real M.  The admission controller runs replicated (identical
    deterministic arithmetic per device — padding lanes are excluded via a
    static ``valid`` mask so they are never admitted); only the slot-reset
    mask is sliced down to the local shard.  ``None`` lowers the exact
    churn-free program.
    """
    from jax.sharding import PartitionSpec as P

    from repro.serving.simulator import clamp_route_by_avail
    from repro.sharding.compat import pad_leading, shard_map

    m = obs_seq.z.shape[1]
    n_dev = mesh.shape[mesh_axis]
    pad = (-m) % n_dev
    m_pad = m + pad
    if hierarchical:
        if hedge is not None:
            raise ValueError("hierarchical sharding cannot hedge (the "
                             "deadline quantile is a global order statistic)")
        if n_edge % n_dev or n_cloud % n_dev:
            raise ValueError(
                f"hierarchical sharding partitions the server pool "
                f"statically: n_edge={n_edge} and n_cloud={n_cloud} must "
                f"both divide by the {n_dev}-device mesh")
    e_l, c_l = n_edge // n_dev, n_cloud // n_dev

    pad_streams = lambda x: pad_leading(x, pad, axis=1)
    # lat_mult is per-task: the hierarchical realization consumes it on the
    # local shard, the gathered one on the replicated real-M batch
    lat_mult = obs_seq.lat_mult
    if hierarchical and lat_mult is not None:
        lat_mult = pad_streams(lat_mult)
    obs_seq = Observation(
        z=pad_streams(obs_seq.z),
        aq=pad_streams(obs_seq.aq),
        dx=pad_streams(obs_seq.dx) if has_dx else None,
        bw_mult=obs_seq.bw_mult,
        u=obs_seq.u,
        # the remaining scenario fields stay replicated: tier_ok / bw_scale
        # feed the per-stream decision and the repair budget, avail the
        # realization tail (sliced per shard in hierarchical mode) — none
        # of them shard over streams
        tier_ok=obs_seq.tier_ok,
        avail=obs_seq.avail,
        lat_mult=lat_mult,
        bw_scale=obs_seq.bw_scale,
        arrive_n=obs_seq.arrive_n,
        # the departure trace feeds the replicated admission arithmetic at
        # padded width (pad lanes never alive, so their entries are inert)
        depart=None if obs_seq.depart is None else pad_streams(obs_seq.depart),
    )
    if not policy.state_replicated:
        state = policy.pad_state(state, pad)
    if churn is not None:
        alive0, degr0, queue0 = churn
        churn = (pad_leading(alive0, pad), pad_leading(degr0, pad), queue0)
    sys = policy.lat.sys
    total_bw = jnp.asarray(sys.total_bw_mbps, jnp.float32)
    valid = jnp.arange(m_pad) < m

    def shard_body(pol, st_l, churn_c, dx_l, z_l, aq_l, bwm_seq, u_seq,
                   scn_seq, churn_seq):
        bw_floor = pol.lat.bw[0, 0, :].max()
        m_local = z_l.shape[1]
        start = jax.lax.axis_index(mesh_axis) * m_local
        slice_l = lambda x: jax.lax.dynamic_slice(x, (start,), (m_local,))
        valid_l = slice_l(valid)
        if pol.state_replicated:
            # the one O(M) gather a global-memory policy needs, ONCE at run
            # start (outside the scan): preseed the replicated table from
            # the gathered round-0 batch
            g0 = lambda x: jax.lax.all_gather(
                x[0], mesh_axis, axis=0, tiled=True)[:m]
            t0 = None if scn_seq[0] is None else scn_seq[0][0]
            st_l = pol.preseed_sharded(st_l, g0(z_l), g0(aq_l), tier_ok=t0)

        def body(c, xs):
            st, churn_c = c
            dx, z, aq, bwm, u, scn, chn = xs
            tier_ok, avail, lat_mult, bw_scale = scn
            task_mask = degr_l = None
            churn_out = {}
            if churn_c is not None:
                alive, degr, queue = churn_c
                arr_n, dep = chn
                budget = capacity_budget(sys, tier_ok=tier_ok,
                                         bw_scale=bw_scale)
                budget = total_bw if budget is None else budget
                alive, degr, queue, newly, admitted, dropped = _churn_admit(
                    alive, degr, queue, arr_n, dep, budget, total_bw,
                    bw_floor, acfg, valid)
                # only this device's slice of the reset mask touches the
                # local carry
                st = pol.reset_streams(st, slice_l(newly))
                churn_c = (alive, degr, queue)
                task_mask = alive[:m]
                degr_l = slice_l(degr)
                churn_out = dict(queue_depth=queue, admitted=admitted,
                                 dropped=dropped)
            obs_l = Observation(z=z, aq=aq, dx=dx, tier_ok=tier_ok)
            st, sol = pol.decide_stream(st, obs_l)

            if hierarchical:
                # -- hierarchical tail: O(n_devices) scalars only ---------
                mask_l = (valid_l if churn_c is None
                          else slice_l(churn_c[0]))
                if degr_l is not None:
                    sol = dict(sol, **{
                        k: jnp.where(degr_l, jnp.zeros_like(sol[k]), sol[k])
                        for k in ("r", "p", "v")})
                sol = pol.repair_local(sol, z, aq, axis_name=mesh_axis,
                                       tier_ok=tier_ok, bw_scale=bw_scale,
                                       task_mask=mask_l)
                tier_frac = avail_l = None
                route_c = sol["route"].astype(jnp.int32)
                if avail is not None:
                    # this shard's statically partitioned server-pool slice
                    avail_l = jnp.concatenate([
                        jax.lax.dynamic_slice(
                            avail[:n_edge],
                            (jax.lax.axis_index(mesh_axis) * e_l,), (e_l,)),
                        jax.lax.dynamic_slice(
                            avail[n_edge:],
                            (jax.lax.axis_index(mesh_axis) * c_l,), (c_l,)),
                    ])
                    route_c = clamp_route_by_avail(route_c, avail_l, e_l, c_l)
                    n_alive_g = jnp.stack([avail[:n_edge].sum(),
                                           avail[n_edge:].sum()])
                    tier_frac = n_alive_g / jnp.asarray(
                        [n_edge, n_cloud], jnp.float32)
                # global fair-share counts: psum of TWO ints per device
                ncl = (route_c * mask_l).sum()
                n_tier_g = jax.lax.psum(
                    jnp.stack([mask_l.sum() - ncl, ncl]), mesh_axis)
                obs_r = Observation(z=z, aq=aq, bw_mult=bwm, u=u,
                                    avail=avail_l, lat_mult=lat_mult)
                met = _realize_obs(pol.lat.sys, obs_r, sol, e_l, c_l, None,
                                   task_mask=mask_l, n_tier=n_tier_g,
                                   tier_frac=tier_frac)
                out = _round_output(sol, met)
                if churn_c is not None:
                    out["route"] = met["route"]
                    out["alive"] = mask_l
                return (st, churn_c), (out, churn_out)

            # -- gathered tail (the parity oracle): cross-task repair +
            # realization on the gathered REAL batch (padding dropped) —
            # identical arithmetic to the dense path on every device
            gather = lambda x: jax.lax.all_gather(
                x, mesh_axis, axis=0, tiled=True)[:m]
            z_g, aq_g = gather(z), gather(aq)
            sol_g = {k: gather(v) for k, v in sol.items()}
            if churn_c is not None:
                degr_m = churn_c[1][:m]
                sol_g = dict(sol_g, **{
                    k: jnp.where(degr_m, jnp.zeros_like(sol_g[k]), sol_g[k])
                    for k in ("r", "p", "v")})
            sol_g = pol.repair(sol_g, z_g, aq_g, tier_ok=tier_ok,
                               bw_scale=bw_scale, task_mask=task_mask)
            obs_g = Observation(z=z_g, aq=aq_g, bw_mult=bwm, u=u,
                                avail=avail, lat_mult=lat_mult)
            met = _realize_obs(pol.lat.sys, obs_g, sol_g, n_edge, n_cloud,
                               hedge, task_mask=task_mask)
            out = _round_output(sol_g, met)
            if churn_c is not None:
                out["route"] = met["route"]
                out["alive"] = task_mask
                out.update(churn_out)
            return (st, churn_c), out

        (st_l, churn_c), mets = jax.lax.scan(
            body, (st_l, churn_c),
            (dx_l, z_l, aq_l, bwm_seq, u_seq, scn_seq, churn_seq))
        return st_l, churn_c, mets

    state_spec = P() if policy.state_replicated else P(mesh_axis)
    dx_spec = P(None, mesh_axis) if has_dx else P()
    lat_spec = (P(None, mesh_axis)
                if hierarchical and obs_seq.lat_mult is not None else P())
    scn_seq = (obs_seq.tier_ok, obs_seq.avail, obs_seq.lat_mult,
               obs_seq.bw_scale)
    churn_seq = (None if churn is None
                 else (obs_seq.arrive_n, obs_seq.depart))
    # hierarchical metrics come out split: per-task leaves stay sharded
    # over streams, the admission scalars replicated
    mets_spec = (P(None, mesh_axis), P()) if hierarchical else P()
    final_state, final_churn, mets = shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), state_spec, P(), dx_spec, P(None, mesh_axis),
                  P(None, mesh_axis), P(), P(), (P(), P(), lat_spec, P()),
                  P()),
        out_specs=(state_spec, P(), mets_spec), check_vma=False,
    )(policy, state, churn, obs_seq.dx, obs_seq.z, obs_seq.aq,
      obs_seq.bw_mult, obs_seq.u, scn_seq, churn_seq)
    if hierarchical:
        per_task, scalars = mets
        mets = {k: v[:, :m] for k, v in per_task.items()}
        mets.update(scalars)
    if not policy.state_replicated:
        final_state = jax.tree_util.tree_map(lambda x: x[:m], final_state)
    if final_churn is not None:
        alive_f, degr_f, queue_f = final_churn
        final_churn = (alive_f[:m], degr_f[:m], queue_f)
    return final_state, final_churn, mets


# ---------------------------------------------------------------------------
# The session
# ---------------------------------------------------------------------------
class ServeSession:
    """Stateful owner of one policy's serving run.

    Parameters
    ----------
    policy : Policy
        Any registered policy (``make_policy``).  Carries the
        SystemConfig / GateConfig / RouterConfig bundle and the kernel
        ``force=`` preference; pass ``force=`` here to override the pin for
        the whole session.
    n_streams : int
        The stream/task batch size M the carry is sized for.
    sim : SimConfig, optional
        Realization-side configuration (server pool sizes).  ``n_edge`` /
        ``n_cloud`` override its fields.
    mesh, mesh_axis : optional
        Default mesh for ``run`` (``run_sharded`` takes an explicit one).
    finetune : FinetuneConfig, optional
        Enable the online gate fine-tuning carry (gate-mode r2evid only).
    hedge : (quantile, cost) tuple, optional
        Enable hedged dispatch inside the realization: a backup replica
        fires at the ``quantile`` deadline of the primary latency draws and
        the earlier finisher wins (+``cost`` dispatch overhead).  Only
        meaningful when the stream carries ``lat_mult`` draws (scenario
        engine); static — part of the compilation key.
    pools : dict, optional
        Tier -> :class:`~repro.serving.pools.ModelPool` live endpoints;
        ``dispatch`` maps a routed solution's token workloads onto them.
    n_slots : int or dict, optional
        Each pool's cache-slot slab: one int for every pool, or
        ``{tier: slots}``; by default each pool's own ``n_slots`` (16).
    admission : AdmissionConfig, optional
        Enable the slot-pool churn path: ``n_streams`` becomes the slot
        capacity M_cap and ``run`` expects ``arrive_n`` / ``depart`` traces
        on the stream.  The admission controller, slot recycling and
        alive-lane masking all run inside the one compiled scan.
    hierarchical : bool
        Default tail mode for :meth:`run_sharded`: ``True`` repairs and
        realizes per shard with only O(n_devices) scalars exchanged each
        round (hierarchical C6 sub-budgets + partitioned server pools),
        ``False`` (default) all-gathers the real-M batch — the parity
        oracle.  See :func:`_serve_run_sharded`.
    """

    def __init__(self, policy: Policy, n_streams: int, *,
                 sim: SimConfig | None = None,
                 n_edge: int | None = None, n_cloud: int | None = None,
                 mesh=None, mesh_axis: str = "data",
                 finetune: FinetuneConfig | None = None,
                 hedge: tuple | None = None,
                 admission: AdmissionConfig | None = None,
                 hierarchical: bool = False,
                 force: str | None = None, pools=None, state=None,
                 n_slots=None):
        if force is not None and hasattr(policy, "force"):
            policy = dataclasses.replace(policy, force=force)
        sim = sim or SimConfig()
        if hedge is not None:
            hq, hc = hedge   # must be a static (quantile, cost) pair
            hedge = (float(hq), float(hc))
            if not 0.0 < hedge[0] < 1.0:
                raise ValueError(f"hedge quantile must be in (0, 1), "
                                 f"got {hedge[0]}")
        self.policy = policy
        self.n_streams = n_streams
        self.sim_cfg = sim
        self.n_edge = sim.n_edge_servers if n_edge is None else n_edge
        self.n_cloud = sim.n_cloud_servers if n_cloud is None else n_cloud
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.pools = pools
        self.n_slots = n_slots
        self._executor = None
        self.finetune = finetune
        self.hedge = hedge
        self.admission = admission
        self.hierarchical = hierarchical
        self._churn_carry = None
        self.state = policy.init(n_streams) if state is None else state
        self._rounds_done = jnp.zeros((), jnp.int32)
        if finetune is not None:
            if getattr(policy, "gate_params", None) is None:
                raise ValueError(
                    "finetune requires a gate-mode r2evid policy "
                    "(gate_params must be set)")
            # the proximal anchor: the offline parameters at session start
            self._anchor = jax.tree_util.tree_map(jnp.copy, policy.gate_params)
            # the finetune carry is donated every run — the session must own
            # its parameter buffers, not alias the caller's policy
            self.policy = dataclasses.replace(
                policy,
                gate_params=jax.tree_util.tree_map(jnp.copy, policy.gate_params))

    # -- config bundle accessors -------------------------------------------
    @property
    def sys_cfg(self):
        return self.policy.lat.sys

    @property
    def gate_params(self):
        return getattr(self.policy, "gate_params", None)

    # ----------------------------------------------------------------------
    def reset(self, n_streams: int | None = None):
        if n_streams is not None:
            self.n_streams = n_streams
        self.state = self.policy.init(self.n_streams)
        self._churn_carry = None
        self._rounds_done = jnp.zeros((), jnp.int32)

    def _churn_init(self):
        """Fresh slot-pool carry: the first ``init_alive`` slots occupied
        (all of them by default), no degrade pins, empty queue."""
        m = self.n_streams
        k = m if self.admission.init_alive is None \
            else min(self.admission.init_alive, m)
        return (jnp.arange(m) < k, jnp.zeros((m,), bool),
                jnp.zeros((), jnp.int32))

    def _check_churn(self, stream: Observation):
        if (stream.arrive_n is None) != (stream.depart is None):
            raise ValueError(
                "churn needs BOTH arrive_n and depart on the stream "
                "(one without the other is almost certainly a trace bug)")
        has_churn = stream.arrive_n is not None
        if has_churn and self.admission is None:
            raise ValueError(
                "stream carries churn traces (arrive_n/depart) but the "
                "session has no AdmissionConfig — pass admission= to "
                "ServeSession")
        if has_churn and self.finetune is not None:
            raise NotImplementedError(
                "online fine-tuning under stream churn is not supported")
        if has_churn and self.hedge is not None:
            raise ValueError(
                "hedged dispatch is not supported under churn (the hedge "
                "fair-share model has no alive-lane masking)")
        return has_churn

    def _check_obs(self, obs: Observation, rounds: bool):
        want = (2, 3) if rounds else (1, 2)
        if obs.z.ndim not in want:
            raise ValueError(f"Observation.z has rank {obs.z.ndim}; "
                             f"expected a {'round-stacked ' if rounds else ''}"
                             f"stream batch")
        if obs.z.shape[-1] != self.n_streams:
            raise ValueError(
                f"Observation carries {obs.z.shape[-1]} streams but the "
                f"session was sized for {self.n_streams}")

    # -- decide-only fast paths (RouterEngine / launch loop) ---------------
    def route(self, obs: Observation):
        """Route one segment batch (no realization).  Returns the solution."""
        with span("r2e.launch"):
            self.state, sol = _decide_step(self.policy, self.state, obs)
        return sol

    def route_many(self, dx_seq, difficulty, acc_req):
        """Route S segment batches in one compiled ``lax.scan``.

        dx_seq: (S, M, d) (or None for gate-free policies); difficulty /
        acc_req: (M,) or (S, M).  Returns the stacked solutions.
        """
        if dx_seq is not None:
            s = dx_seq.shape[0]
        elif difficulty.ndim > 1:
            s = difficulty.shape[0]
        else:
            raise ValueError(
                "route_many cannot infer the segment count: pass dx_seq or "
                "round-stacked (S, M) difficulty/acc_req")
        if difficulty.ndim == 1:
            difficulty = jnp.broadcast_to(difficulty, (s,) + difficulty.shape)
        if acc_req.ndim == 1:
            acc_req = jnp.broadcast_to(acc_req, (s,) + acc_req.shape)
        obs_seq = Observation(z=difficulty, aq=acc_req, dx=dx_seq)
        self.state, sols = _decide_scan(self.policy, self.state, obs_seq)
        return sols

    # -- serve (decide + realize) ------------------------------------------
    def step(self, obs: Observation):
        """One serving round.  With ``bw_mult``/``u`` on the observation the
        round is realized and (sol+metrics) returned; without them this is
        ``route``."""
        with span("r2e.step"):
            self._check_obs(obs, rounds=False)
            if obs.u is None or obs.bw_mult is None:
                return self.route(obs)
            self.state, out = _serve_step(
                self.policy, self.state, obs, self.n_edge, self.n_cloud,
                self.hedge)
            return out

    def run(self, stream: Observation, n_rounds: int | None = None,
            mesh=None, mesh_axis: str | None = None):
        """Serve R rounds in one compiled scan (realization fused).

        ``stream``: an :class:`Observation` whose fields carry a leading
        round axis — (R, M[, d]) / (R, 2) / (R, K).  Returns the per-round
        metric dict of (R, M) arrays (deterministic delay / energy / cost /
        accuracy plus the decisions); observation noise stays the caller's
        job (it needs host rng state).  ``n_rounds`` slices a prefix.
        With a mesh (argument or session default) the run dispatches to
        :meth:`run_sharded`.
        """
        self._check_obs(stream, rounds=True)
        if stream.u is None or stream.bw_mult is None:
            raise ValueError("session.run needs bw_mult and u on the stream "
                             "(use route_many for decide-only scans)")
        if n_rounds is not None:
            stream = jax.tree_util.tree_map(lambda x: x[:n_rounds], stream)
        mesh = self.mesh if mesh is None else mesh
        if mesh is not None:
            return self.run_sharded(mesh, stream,
                                    mesh_axis=mesh_axis or self.mesh_axis)
        if self._check_churn(stream):
            if self._churn_carry is None:
                self._churn_carry = self._churn_init()
            alive, degr, queue = self._churn_carry
            carry = (self.state, alive, degr, queue)
            (self.state, alive, degr, queue), mets = _serve_run_churn(
                self.policy, carry, stream, self.admission, self.n_edge,
                self.n_cloud)
            self._churn_carry = (alive, degr, queue)
            return mets
        if self.finetune is not None:
            carry = (self.state, self.policy.gate_params, self._rounds_done)
            (self.state, params, self._rounds_done), mets = \
                _serve_run_finetune(self.policy, carry, stream, self._anchor,
                                    self.finetune, self.n_edge, self.n_cloud,
                                    self.hedge)
            self.policy = dataclasses.replace(self.policy, gate_params=params)
            return mets
        self.state, mets = _serve_run(
            self.policy, self.state, stream, self.n_edge, self.n_cloud,
            self.hedge)
        return mets

    def lower_run(self, stream: Observation):
        """The lowered program a plain (dense, churn- and finetune-free)
        :meth:`run` executes on ``stream`` — for inspection: which kernels
        it holds, its compile time and memory."""
        self._check_obs(stream, rounds=True)
        return _serve_run.lower(self.policy, self.state, stream, self.n_edge,
                                self.n_cloud, self.hedge)

    def run_sharded(self, mesh, stream: Observation,
                    n_rounds: int | None = None, mesh_axis: str = "data",
                    hierarchical: bool | None = None):
        """The whole run as ONE compiled sharded scan over the stream axis.

        In the default gathered mode, metrics and the final carry are
        identical to the dense :meth:`run` (the cross-task tail runs on the
        all-gathered real-M batch); M pads to any device count.
        ``hierarchical=True`` (or the session default) switches the
        cross-task tail to per-shard sub-budget repair + partitioned-pool
        realization with O(n_devices) scalar exchange per round — exact C6,
        per-shard queueing (see docs/SHARDING.md).
        """
        self._check_obs(stream, rounds=True)
        if hierarchical is None:
            hierarchical = self.hierarchical
        if stream.u is None or stream.bw_mult is None:
            raise ValueError("session.run_sharded needs bw_mult and u on "
                             "the stream")
        if not self.policy.shardable:
            raise ValueError(
                f"policy {self.policy.name!r} couples tasks globally in "
                f"decide_stream and cannot run stream-sharded")
        if self.finetune is not None:
            raise NotImplementedError(
                "online fine-tuning is single-mesh only for now")
        if hierarchical and self.hedge is not None:
            raise ValueError(
                "hierarchical sharding cannot hedge: the deadline quantile "
                "is a global order statistic (use the gathered mode)")
        if n_rounds is not None:
            stream = jax.tree_util.tree_map(lambda x: x[:n_rounds], stream)
        has_churn = self._check_churn(stream)
        churn = acfg = None
        if has_churn:
            if self._churn_carry is None:
                self._churn_carry = self._churn_init()
            churn, acfg = self._churn_carry, self.admission
        self.state, churn, mets = _serve_run_sharded(
            self.policy, self.state, stream, self.n_edge, self.n_cloud,
            mesh, mesh_axis, stream.dx is not None, self.hedge,
            churn, acfg, hierarchical)
        if has_churn:
            self._churn_carry = churn
        return mets

    def run_elastic(self, stream: Observation, failures: dict, *,
                    mesh_axis: str = "data", n_nodes: int | None = None):
        """Serve through mid-run device loss: one sharded scan per epoch.

        ``failures``: {round -> iterable of node ids} killed *before* that
        round.  The run is segmented at failure boundaries; at each boundary
        the dead nodes are registered with a :class:`ClusterSim`,
        ``elastic_remesh(alive, prefer="data")`` rebuilds the survivor mesh,
        and the next segment continues under it with the carried stream
        state — the serving analogue of the trainer's restore-on-remesh
        recovery path.  Returns the per-round metrics concatenated across
        segments (identical keys to :meth:`run`); the mesh history is kept
        on ``self.mesh_history``.
        """
        import numpy as np

        from repro.runtime.cluster import ClusterSim, elastic_remesh

        self._check_obs(stream, rounds=True)
        r_total = stream.z.shape[0]
        cluster = ClusterSim(n_nodes or len(jax.devices()))
        # a malformed plan silently skipped here would make the run look
        # healthier than the experiment the caller asked for — fail loudly
        for r, nodes in failures.items():
            if not isinstance(r, (int, np.integer)) or not 0 < r < r_total:
                raise ValueError(
                    f"failures round {r!r} is outside the valid boundary "
                    f"range 1..{r_total - 1} (failures fire *before* a "
                    f"round; round 0 has no prior segment)")
            for node in nodes:
                if not 0 <= int(node) < cluster.n_nodes:
                    raise ValueError(
                        f"failures[{r}] names unknown node {node!r}; "
                        f"cluster has nodes 0..{cluster.n_nodes - 1}")
        bounds = sorted(failures)
        mesh = elastic_remesh(cluster.alive, prefer="data")
        self.mesh_history = [(0, mesh)]
        parts, start = [], 0
        for b in bounds + [r_total]:
            seg = jax.tree_util.tree_map(lambda x: x[start:b], stream)
            # segment metrics land on that epoch's mesh — pull them to host
            # so epochs served on different survivor sets concatenate
            parts.append({k: np.asarray(v) for k, v in
                          self.run_sharded(mesh, seg,
                                           mesh_axis=mesh_axis).items()})
            if b < r_total:
                for node in failures[b]:
                    cluster.kill(int(node))
                if cluster.alive <= 0:
                    raise RuntimeError(
                        f"all {cluster.n_nodes} nodes dead at round {b}; "
                        f"no survivor mesh to continue on")
                mesh = elastic_remesh(cluster.alive, prefer="data")
                self.mesh_history.append((b, mesh))
                # re-shard the carried per-stream state onto the survivors
                self.state = jax.tree_util.tree_map(
                    lambda x: jnp.asarray(np.asarray(x)), self.state)
            start = b
        return {k: jnp.asarray(np.concatenate([p[k] for p in parts], axis=0))
                for k in parts[0]}

    # -- live model pools ---------------------------------------------------
    def _make_executor(self):
        from repro.serving.dispatch import DispatchExecutor

        # slab sized for the largest fidelity the router can choose:
        # dispatch sizes prompts as 16·(1+r) with r < n_res; n_slots per
        # tier as DispatchExecutor takes it (None: each pool's own)
        return DispatchExecutor(
            self.pools, n_slots=self.n_slots,
            max_prefill_len=16 * self.sys_cfg.n_res)

    @property
    def executor(self):
        """The lazily built continuous-batching dispatch executor
        (:mod:`repro.serving.dispatch`) over the attached pools."""
        if self.pools is None:
            raise ValueError("session has no pools attached")
        if self._executor is None:
            self._executor = self._make_executor()
        return self._executor

    def dispatch(self, sol, decode_tokens: int = 8, serial: bool = False):
        """Execute a routed solution on the attached tier pools.

        Default: every routed segment becomes a :class:`Request` sized by
        ITS OWN chosen fidelity (``16·(1+r_i)`` prompt tokens) and the
        continuous-batching executor serves them — bucketed prefills,
        token-level decode across all in-flight segments per tier, tiers
        interleaved.  Dead lanes (``route == -1``, churned slots) are never
        enqueued.  Returns {tier: stats dict} with per-request latency
        p50/p99 and tokens/s (see ``DispatchExecutor.serve``).

        ``serial=True`` is the deprecated pre-executor path, kept as the
        scheduling oracle: one eager prefill+decode per tier, every
        segment sized by the tier-MEAN fidelity (the historical behavior —
        wrong for mixed-fidelity tiers, which is why it is no longer the
        default).  Returns the old bare {tier: n_segments} counts.
        """
        if self.pools is None:
            raise ValueError("session has no pools attached")
        import numpy as np

        if serial:
            served = {}
            for tier in (0, 1):
                idx = np.where(np.asarray(sol["route"]) == tier)[0]
                if len(idx) == 0:
                    continue
                # token budget scales with chosen fidelity (resolution x fps)
                n_tok = 16 * (1 + int(np.asarray(sol["r"])[idx].mean()))
                toks = jnp.ones((len(idx), n_tok), jnp.int32)
                self.pools[tier].serve_segment(toks,
                                               decode_tokens=decode_tokens)
                served[tier] = len(idx)
            return served

        from repro.serving.dispatch import Request

        route = np.asarray(sol["route"])
        r = np.asarray(sol["r"])
        reqs = []
        for i in range(route.shape[0]):
            tier = int(route[i])
            if tier < 0:        # churned / dead lane — never enqueued
                continue
            n_tok = 16 * (1 + int(r[i]))     # per-segment fidelity sizing
            vocab = self.pools[tier].cfg.vocab_size
            toks = (i * 131 + np.arange(n_tok)) % vocab
            reqs.append(Request(stream=i, tier=tier,
                                tokens=toks.astype(np.int32),
                                decode_tokens=decode_tokens))
        return self.executor.serve(reqs)

    def feedback(self):
        """The executor's measured per-tier serving state (see
        ``DispatchExecutor.feedback``)."""
        return self.executor.feedback()

    def apply_feedback(self, obs: Observation) -> Observation:
        """Fold the executor's measured per-tier state into an observation —
        the router ↔ serving loop the paper's Stage-2 assumes.

        The measured multiplier lands twice: on ``bw_mult`` (the realization
        sees the congested uplink) and, capacity-weighted across tiers, on
        ``bw_scale`` (the C6 repair plans against the shrunken budget — this
        is what actually changes the next round's decisions).  A session
        whose pools kept up returns the observation unchanged.
        """
        fb = self.feedback()
        mult = jnp.asarray(fb["bw_mult"], jnp.float32)[:2]
        sys = self.sys_cfg
        cap = sys.edge_bw_mbps + sys.cloud_bw_mbps
        scale = (sys.edge_bw_mbps * mult[0] + sys.cloud_bw_mbps * mult[1]) / cap
        if obs.z is not None and jnp.ndim(obs.z) >= 2:
            # round-stacked stream: every leaf needs the leading round axis
            # for the serve scan, so the (constant) measured state is tiled
            r = obs.z.shape[0]
            mult_seq = jnp.broadcast_to(mult, (r, 2))
            scale_seq = jnp.broadcast_to(scale, (r,))
        else:
            mult_seq, scale_seq = mult, scale
        return dataclasses.replace(
            obs,
            bw_mult=mult_seq if obs.bw_mult is None else obs.bw_mult * mult,
            bw_scale=scale_seq if obs.bw_scale is None else obs.bw_scale * scale,
        )
