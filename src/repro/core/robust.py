"""Two-stage robust optimization (paper §3.1/§3.3, Eq. 2-10, Alg. 2).

Decision lattice per task: first stage y=(route∈{edge,cloud}, r∈R, p∈P)
(50 options), second stage v∈V (K=5 model versions).  The Γ-budget
polyhedral uncertainty set (Eq. 9)

    U = { u : u_k = g_k·ũ_k,  g_k∈[0,1],  Σ_k g_k ≤ Γ }

scales the second-stage cost of model k by (1+u_k) (compute-time deviation
under load/network fluctuation).  By Bertsimas-style strong duality the
worst-case u sits at a pole of U (Eq. 10), so SP is solved *exactly* by pole
enumeration (K=5 ⇒ 2^K = 32 subset poles, filtered to |S| ≤ Γ), and the
column-and-constraint master (Alg. 2) alternates:

    MP1 : y* = argmin_y c1(y) + η(y),  η(y) = max over generated scenarios
          of the recourse value  min_v b2(v; y)·(1+u_j,v)
    SP  : u_{j+1} = argmax_{u∈poles} min_{v feasible} b2(v; y*)·(1+u_v)

until O_up − O_down ≤ θ.  The production solver (:func:`solve_ccg`) runs the
alternation as a *fixed-unroll masked iteration* over the whole task batch:
the scenario set is bounded by the pole count P (an iteration that adds no
new pole has converged), so at most min(max_iters, P+1) masked
master/adversary updates suffice, with a ``done`` flag freezing converged
lanes.  No ``lax.while_loop`` is lowered — the solver is a straight chain of
batched reductions, fully fusable under ``vmap``/``scan``/``shard_map``, and
the hot master reduction dispatches to the Pallas ``ccg_master`` kernel on
TPU.  :func:`solve_ccg_while` keeps the original per-task ``while_loop``
solver as the decision-identity oracle; ``exact_oracle`` brute-forces
min_y max_u min_v for tests.

All flattened-index bookkeeping lives in :class:`DecisionLattice`
(``repro.core.lattice``) — this module never reshapes the lattice itself.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.cost_model import SystemConfig
from repro.core.lattice import DecisionLattice
from repro.kernels.ccg_encode.ops import ccg_encode
from repro.kernels.ccg_master.ops import ccg_master
from repro.kernels.ccg_master.ref import BIG  # shared infeasibility sentinel
from repro.kernels.ccg_solve.ops import ccg_solve


def _poles(num_versions: int, gamma: int):
    """All subset poles of U with |S| <= gamma: (P, K) in {0,1}."""
    k = num_versions
    masks = []
    for bits in range(2 ** k):
        s = [(bits >> i) & 1 for i in range(k)]
        if sum(s) <= gamma:
            masks.append(s)
    return jnp.asarray(masks, jnp.float32)  # (P, K)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("lat", "poles", "rec_table", "b2_scaled"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class RobustProblem:
    lat: DecisionLattice
    poles: jnp.ndarray     # (P, K) pole indicators
    # (P, F, 2^K) recourse lookup: min_v b2·(1+u_v) over the feasible-version
    # subset encoded as a bitmask.  Task-independent (depends only on the
    # lattice costs, poles, and ũ), built once; the per-task CCG sweep then
    # reduces to encoding its (F, K) feasibility mask and gathering.
    rec_table: jnp.ndarray
    # (P, F, K) pole-scaled second-stage costs b2·(1+u) — the unexpanded form
    # of the same lookup; the Pallas encode kernel keeps this slab
    # VMEM-resident and min-folds it instead of gathering rec_table
    b2_scaled: jnp.ndarray

    @classmethod
    def build(cls, sys: SystemConfig):
        lat = DecisionLattice.build(sys)
        poles = _poles(sys.num_versions, sys.gamma)
        u_all = poles * lat.u_dev                             # (P, K)
        b2_scaled = lat.b2_flat[None] * (1.0 + u_all[:, None, :])  # (P, F, K)
        k = sys.num_versions
        masks = ((jnp.arange(2 ** k)[:, None] >> jnp.arange(k)[None]) & 1).astype(bool)
        rec_table = jnp.where(
            masks[None, None], b2_scaled[:, :, None, :], BIG
        ).min(axis=-1)                                        # (P, F, 2^K)
        return cls(lat=lat, poles=poles, rec_table=rec_table,
                   b2_scaled=b2_scaled)

    @property
    def sys(self) -> SystemConfig:
        return self.lat.sys

    @property
    def u_dev(self):
        """(K,) max deviations ũ_k — single source of truth is the lattice."""
        return self.lat.u_dev

    # back-compat views of the cost tables (natural layout)
    @property
    def c1(self):
        return self.lat.c1

    @property
    def b2(self):
        return self.lat.b2


def _encode_tasks(prob: RobustProblem, difficulty, acc_req, tier_ok=None):
    """Table-based per-task CCG inputs — the encode ORACLE.

    Builds the full (M, F, K) accuracy tensor via the broadcast table, then
    derives the feasibility masks and gathers the recourse slab.  Kept for
    the while_loop oracle and the ``ccg_encode`` parity tests; the serving
    hot path uses :func:`_encode_tasks_fused` (bit-identical, table-free).
    ``tier_ok``: optional (..., 2) per-tier availability — outaged tiers'
    options drop to -BIG accuracy (infeasible, out of any fallback argmax).
    Returns ``(f_flat, feas_f, fs_ok, rec_all)`` with shapes
    ((M, F, K), (M, F, K), (M, F), (M, P, F)).
    """
    lat = prob.lat
    sys = lat.sys
    # C1 protected with the robust accuracy margin (h in the Benders cuts)
    f_flat, feas_f = lat.feasible_flat(difficulty, acc_req,
                                       sys.acc_margin_robust, tier_ok=tier_ok)
    pow2 = 2 ** jnp.arange(sys.num_versions)
    code = (feas_f * pow2[None, None]).sum(axis=-1)   # (M, F) subset codes
    rec_all = jnp.take_along_axis(
        prob.rec_table[None], code[:, None, :, None], axis=-1
    )[..., 0]                                         # (M, P, F)
    return f_flat, feas_f, feas_f.any(axis=-1), rec_all


def _encode_tasks_fused(prob: RobustProblem, difficulty, acc_req,
                        force: str = "auto", tier_ok=None):
    """Table-free per-task CCG inputs via the fused ``ccg_encode`` kernel.

    No (M, N, Z, K, 2) or (M, F, K) accuracy tensor is built anywhere:
    the kernel/ref evaluate the accuracy formula per version directly in the
    flat layout, emit the (M, F) feasible-version bitmask ``code``, the
    (M, P, F) recourse slab, and the flat accuracy argmax ``best`` consumed
    by the all-infeasible fallback.  Bit-identical to :func:`_encode_tasks`
    (parity-tested in tests/test_kernels.py).  ``tier_ok``: optional (2,)
    per-tier availability, lowered to the kernel's (F,) ``y_ok`` mask.
    """
    lat = prob.lat
    y_ok = None if tier_ok is None else lat.tier_y_ok(tier_ok)
    return ccg_encode(
        jnp.asarray(difficulty, jnp.float32), jnp.asarray(acc_req, jnp.float32),
        lat.rn_flat, lat.pn_flat, lat.tier_flat,
        prob.b2_scaled, prob.rec_table,
        margin=lat.sys.acc_margin_robust, num_versions=lat.sys.num_versions,
        force=force, y_ok=y_ok,
    )


def _finish_solution(prob: RobustProblem, code, best, rec_all, y_f):
    """Shared epilogue: final recourse v*, infeasibility fallback, unflatten.

    y_f: (M,) converged first-stage indices; code: the (M, F) feasibility
    bitmask; best: (M,) flat accuracy argmax.  Picks v* at the worst pole of
    y_f, then applies the graceful margin relaxation (tasks infeasible *with*
    the robust margin fall back to the max-accuracy configuration).  All
    per-task work is O(M) gathers and bit tests — no accuracy table.
    """
    lat = prob.lat
    sys = lat.sys
    b2 = lat.b2_flat
    sp_vals = jnp.take_along_axis(rec_all, y_f[:, None, None], axis=2)[..., 0]
    worst = sp_vals.argmax(axis=1)                    # (M,)
    u = prob.poles[worst] * prob.u_dev[None]          # (M, K)
    code_y = jnp.take_along_axis(code, y_f[:, None], axis=1)[:, 0]
    feas_y = ((code_y[:, None] >> jnp.arange(sys.num_versions)[None]) & 1) > 0
    vals = jnp.where(feas_y, b2[y_f] * (1.0 + u), BIG)
    v_star = vals.argmin(axis=1)
    none_ok = ~(code > 0).any(axis=1)
    y_f = jnp.where(none_ok, best // sys.num_versions, y_f)
    v_star = jnp.where(none_ok, best % sys.num_versions, v_star)
    route, r_idx, p_idx = lat.unflatten_index(y_f)
    return route, r_idx, p_idx, v_star, none_ok


@partial(jax.jit, static_argnames=("max_iters", "force"))
def solve_ccg(prob: RobustProblem, difficulty, acc_req, max_iters: int = 8,
              theta: float = 1e-4, warm_y=None, force: str = "auto",
              tier_ok=None):
    """Alg. 2 for a batch of tasks — fixed-unroll masked iteration.

    difficulty: (M,) content difficulty z; acc_req: (M,) A^q_i.
    Returns dict with y (route), r, p, v indices + objective bounds.

    Instead of a per-task ``lax.while_loop`` (whose batched lowering carries
    ~1 ms of fixed overhead per call on CPU and blocks fusion), the CCG
    alternation is unrolled min(max_iters, P+1) times over the *whole* batch:
    each SP step either adds a new pole to a task's scenario set or proves
    convergence, so P+1 masked steps are exact, and a ``done`` flag freezes
    converged lanes (their state stops updating, exactly as if the loop had
    exited).  Decisions, bounds, and iteration counts are bit-identical to
    :func:`solve_ccg_while`.

    The master reduction (η-max over generated scenarios, feasibility mask,
    argmin over F) dispatches to the Pallas ``ccg_master`` kernel on TPU,
    which keeps the whole (P, F) recourse slab VMEM-resident per tile.  Off
    TPU the same master is computed incrementally: η is a running (M, F) max
    folded in as each pole is generated (max is exact in floats, so the
    running form is bit-identical to the masked slab reduction) — O(M·F) per
    iteration instead of O(M·P·F).  The per-task inputs come from the fused
    table-free ``ccg_encode`` kernel (accuracy formula → feasibility bitmask
    → recourse slab in one pass; no (M, F, K) tensor anywhere).  ``force``
    pins both the encode and master implementations for tests: "pallas"
    (compiled), "interpret" (the Pallas interpreter) and "ref" exercise the
    kernel ops, "auto" picks the backend default.

    ``warm_y``: optional (M,) flat first-stage warm starts (the Stage-1
    route).  When given, each task's scenario set is seeded with the exact
    worst-case pole of its warm start and O_up starts at that configuration's
    robust cost — a valid upper bound whenever the warm start is feasible —
    so typical tasks converge in fewer CCG iterations.

    ``tier_ok``: optional (2,) per-tier availability; outaged tiers' options
    become infeasible and drop out of the all-infeasible fallback.
    """
    lat = prob.lat
    c1 = lat.c1_flat                                  # (F,)
    code, rec_all, best = _encode_tasks_fused(prob, difficulty, acc_req,
                                              force=force, tier_ok=tier_ok)
    fs_ok = code > 0                                  # (M, F)
    m = code.shape[0]
    n_poles = prob.poles.shape[0]
    if warm_y is None:
        warm_y = -jnp.ones(m, jnp.int32)

    # warm start: seed the scenario set with the warm y's worst pole and
    # start O_up at its robust cost (only when the warm start is usable)
    wy = jnp.maximum(warm_y, 0)
    use_warm = (warm_y >= 0) & jnp.take_along_axis(fs_ok, wy[:, None], axis=1)[:, 0]
    rec_wy = jnp.take_along_axis(rec_all, wy[:, None, None], axis=2)[..., 0]
    warm_pole = rec_wy.argmax(axis=1)                 # (M,)
    warm_up = c1[wy] + jnp.take_along_axis(rec_wy, warm_pole[:, None], axis=1)[:, 0]
    o_up = jnp.where(use_warm, warm_up, BIG)
    o_down = jnp.full((m,), -BIG)
    y_best = wy
    done = jnp.zeros((m,), bool)
    iters = jnp.zeros((m,), jnp.int32)

    # master-step state: the Pallas slab kernel consumes the (M, P) scenario
    # mask against the full recourse slab; the jnp path folds each generated
    # pole into a running (M, F) η-max (bit-identical — max is exact)
    slab_master = force != "auto" or jax.default_backend() == "tpu"
    if slab_master:
        pole_iota = jnp.arange(n_poles)[None, :]      # (1, P)
        scen_mask = jnp.where(
            use_warm[:, None] & (pole_iota == warm_pole[:, None]), 1.0, 0.0)
    else:
        rec_warm = jnp.take_along_axis(
            rec_all, warm_pole[:, None, None], axis=1)[:, 0]       # (M, F)
        eta_run = jnp.where(use_warm[:, None], rec_warm, -BIG)
        has_scen = use_warm

    for _ in range(min(max_iters, n_poles + 1)):
        live = ~done
        # MP1: eta(y) = max over generated scenarios of the recourse value,
        # obj = c1 + eta masked to feasible options, argmin over F
        if slab_master:
            y_star, od_new = ccg_master(rec_all, scen_mask, fs_ok, c1, force=force)
        else:
            eta = jnp.where(has_scen[:, None], eta_run, 0.0)
            obj = jnp.where(fs_ok, c1[None] + eta, BIG)
            y_star = obj.argmin(axis=1).astype(jnp.int32)
            od_new = jnp.take_along_axis(obj, y_star[:, None], axis=1)[:, 0]
        # SP: exact worst-case pole for y_star (Eq. 10 pole optimality)
        sp_vals = jnp.take_along_axis(rec_all, y_star[:, None, None], axis=2)[..., 0]
        worst_pole = sp_vals.argmax(axis=1)           # (M,)
        q = jnp.take_along_axis(sp_vals, worst_pole[:, None], axis=1)[:, 0]
        cand = c1[y_star] + q
        # the returned decision is the INCUMBENT achieving O_up, not the
        # last master argmin — the master's obj only lower-bounds the
        # robust cost, so a θ-tied y_star may be worse than the incumbent
        up_new = jnp.minimum(o_up, cand)
        # freeze converged lanes: done lanes keep their pre-convergence state
        y_best = jnp.where(live & (cand < o_up), y_star, y_best)
        o_down = jnp.where(live, od_new, o_down)
        o_up = jnp.where(live, up_new, o_up)
        if slab_master:
            # add the scenario column as a one-hot max (XLA scatter is slow)
            mask_new = jnp.maximum(
                scen_mask, (pole_iota == worst_pole[:, None]).astype(scen_mask.dtype))
            scen_mask = jnp.where(live[:, None], mask_new, scen_mask)
        else:
            rec_new = jnp.take_along_axis(
                rec_all, worst_pole[:, None, None], axis=1)[:, 0]   # (M, F)
            eta_run = jnp.where(
                live[:, None], jnp.maximum(eta_run, rec_new), eta_run)
            has_scen = has_scen | live
        iters = iters + live.astype(jnp.int32)
        done = jnp.where(live, (up_new - od_new) <= theta, done)

    route, r_idx, p_idx, v_star, none_ok = _finish_solution(
        prob, code, best, rec_all, y_best)
    return {
        "route": route, "r": r_idx, "p": p_idx, "v": v_star,
        "o_up": o_up, "o_down": o_down, "iters": iters, "infeasible": none_ok,
    }


@partial(jax.jit, static_argnames=("max_iters", "theta", "force"))
@jax.named_scope("r2e.ccg")
def solve_ccg_fused(prob: RobustProblem, difficulty, acc_req,
                    max_iters: int = 8, theta: float = 1e-4, warm_y=None,
                    force: str = "auto", tier_ok=None):
    """Alg. 2 as ONE fused solve — the serving hot path since PR 6.

    Same contract as :func:`solve_ccg` (decisions, bounds, and iteration
    counts are bit-identical — parity-locked in tests), but the entire
    alternation (encode → master argmin → SP pole selection → η update,
    min(max_iters, P+1) steps) dispatches to the ``ccg_solve`` kernel triple
    instead of one encode + one master call per unrolled step.  No (M, P, F)
    recourse slab exists anywhere: η is a running (M, F) max and recourse
    values are K-fold masked mins over the (F, K) cost table (exact — see
    kernels/ccg_solve).  The jnp ref is the CPU hot path with a batch-level
    early-exit while_loop + live-lane compaction; the Pallas kernel keeps
    the per-lane solver state VMEM-resident across all steps on TPU.

    ``solve_ccg`` and ``solve_ccg_while`` are retained as the bit-exact
    oracles (and for the slab-master Pallas path's parity tests).

    ``tier_ok``: optional (2,) per-tier availability; outaged tiers' options
    become infeasible and drop out of the all-infeasible fallback.
    """
    lat = prob.lat
    if warm_y is None:
        warm_y = -jnp.ones(jnp.asarray(difficulty).shape[0], jnp.int32)
    y_ok = None if tier_ok is None else lat.tier_y_ok(tier_ok)
    y_f, v_star, o_up, o_down, iters, none_ok = ccg_solve(
        jnp.asarray(difficulty, jnp.float32), jnp.asarray(acc_req, jnp.float32),
        lat.rn_flat, lat.pn_flat, lat.tier_flat, lat.b2_flat,
        prob.poles * lat.u_dev, lat.c1_flat, warm_y.astype(jnp.int32),
        margin=lat.sys.acc_margin_robust, num_versions=lat.sys.num_versions,
        max_iters=max_iters, theta=theta, force=force, y_ok=y_ok)
    route, r_idx, p_idx = lat.unflatten_index(y_f)
    return {
        "route": route, "r": r_idx, "p": p_idx, "v": v_star,
        "o_up": o_up, "o_down": o_down, "iters": iters, "infeasible": none_ok,
    }


@partial(jax.jit, static_argnames=("max_iters",))
def solve_ccg_while(prob: RobustProblem, difficulty, acc_req, max_iters: int = 8,
                    theta: float = 1e-4, warm_y=None, tier_ok=None):
    """Original per-task ``lax.while_loop`` CCG — the unrolled solver's
    decision-identity oracle (kept out of the serving hot path)."""
    lat = prob.lat
    sys = lat.sys
    c1 = lat.c1_flat                                  # (F,)
    b2 = lat.b2_flat                                  # (F, K)
    f_flat, feas_f, _, rec_all_m = _encode_tasks(prob, difficulty, acc_req,
                                                 tier_ok=tier_ok)
    if warm_y is None:
        warm_y = -jnp.ones(feas_f.shape[0], jnp.int32)

    def per_task(feas_i, rec_all, warm_i):
        # any first-stage option with no feasible v is excluded from MP1
        fs_ok = feas_i.any(axis=-1)                      # (F,)

        # warm start: seed the scenario set with the warm y's worst pole and
        # start O_up at its robust cost (only when the warm start is usable)
        use_warm = (warm_i >= 0) & fs_ok[jnp.maximum(warm_i, 0)]
        wy = jnp.maximum(warm_i, 0)
        warm_pole = rec_all[:, wy].argmax()
        warm_up = c1[wy] + rec_all[warm_pole, wy]
        init_mask = jnp.zeros((prob.poles.shape[0],)).at[warm_pole].set(
            jnp.where(use_warm, 1.0, 0.0))
        init_up = jnp.where(use_warm, warm_up, BIG)

        def body(carry):
            it, scen_mask, o_up, _, y_best, done = carry
            # MP1: eta(y) = max over generated scenarios of the recourse value
            active = jnp.where(scen_mask[:, None] > 0, rec_all, -BIG)
            eta = jnp.where(scen_mask.sum() > 0, active.max(axis=0), 0.0)  # (F,)
            obj = jnp.where(fs_ok, c1 + eta, BIG)
            y_star = obj.argmin()
            o_down = obj[y_star]
            # SP: exact worst-case pole for y_star (Eq. 10 pole optimality)
            sp_vals = rec_all[:, y_star]                 # (P,)
            worst_pole = sp_vals.argmax()
            q = sp_vals[worst_pole]
            # the returned decision is the INCUMBENT achieving O_up, not the
            # last master argmin — the master's obj only lower-bounds the
            # robust cost, so a θ-tied y_star may be worse than the incumbent
            # (matters when the warm seed makes convergence fire early)
            y_best = jnp.where(c1[y_star] + q < o_up, y_star, y_best)
            o_up = jnp.minimum(o_up, c1[y_star] + q)
            done = (o_up - o_down) <= theta
            scen_mask = scen_mask.at[worst_pole].set(1.0)  # add scenario column
            return it + 1, scen_mask, o_up, o_down, y_best, done

        def cond(carry):
            it, _, _, _, _, done = carry
            return (it < max_iters) & ~done

        init = (0, init_mask, init_up, jnp.asarray(-BIG),
                wy, jnp.asarray(False))
        it, scen_mask, o_up, o_down, y_star, done = jax.lax.while_loop(cond, body, init)

        # final recourse: worst pole for chosen y, then v*
        sp_vals = rec_all[:, y_star]
        worst = sp_vals.argmax()
        u = prob.poles[worst] * prob.u_dev
        vals = jnp.where(feas_i[y_star], b2[y_star] * (1.0 + u), BIG)
        v_star = vals.argmin()
        return y_star, v_star, o_up, o_down, it

    y_f, v_star, o_up, o_down, iters = jax.vmap(per_task)(feas_f, rec_all_m, warm_y)
    # graceful margin relaxation: tasks infeasible *with* the robust margin
    # fall back to the max-accuracy configuration (which also covers margin-
    # free feasibility when any config clears A^q exactly)
    none_ok = ~feas_f.any(axis=(1, 2))
    best_acc = f_flat.reshape(f_flat.shape[0], -1).argmax(axis=1)
    ba_f = best_acc // sys.num_versions
    ba_v = best_acc % sys.num_versions
    y_f = jnp.where(none_ok, ba_f, y_f)
    v_star = jnp.where(none_ok, ba_v, v_star)
    route, r_idx, p_idx = lat.unflatten_index(y_f)
    return {
        "route": route, "r": r_idx, "p": p_idx, "v": v_star,
        "o_up": o_up, "o_down": o_down, "iters": iters, "infeasible": none_ok,
    }


def solve_ccg_sharded(prob: RobustProblem, difficulty, acc_req, mesh,
                      axis: str = "data", max_iters: int = 8,
                      theta: float = 1e-4, warm_y=None):
    """``solve_ccg`` with the task batch M split across devices.

    The CCG sweep is embarrassingly parallel over tasks (the hoisted
    (P, F, K) recourse table is replicated; only the per-task feasibility
    masks and loop state are local), so a ``shard_map`` over the mesh's data
    axis scales the sweep linearly with device count.  The batch is padded to
    a multiple of the axis size with trivially-feasible dummies and sliced
    back, so any M works.  Decisions are identical to the single-device path.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding.compat import pad_leading, shard_map

    m = difficulty.shape[0]
    n_dev = mesh.shape[axis]
    pad = (-m) % n_dev
    difficulty = pad_leading(difficulty, pad)
    acc_req = pad_leading(acc_req, pad)
    if warm_y is None:
        warm_y = -jnp.ones((m,), jnp.int32)
    warm_y = pad_leading(warm_y, pad, value=-1)

    def shard_fn(pb, z, aq, wy):
        return solve_ccg(pb, z, aq, max_iters=max_iters, theta=theta, warm_y=wy)

    # check_vma=False: the replicated problem tables have no tracked
    # replication rule, but every operand is either axis-sharded or an
    # explicitly replicated input
    sol = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis)),
        out_specs=P(axis), check_vma=False,
    )(prob, difficulty, acc_req, warm_y)
    return {k: v[:m] for k, v in sol.items()}


def exact_oracle(prob: RobustProblem, difficulty, acc_req, tier_ok=None):
    """Brute force min_y max_{u∈poles} min_v — test oracle."""
    lat = prob.lat
    c1 = lat.c1_flat
    b2 = lat.b2_flat
    _, feas_f = lat.feasible_flat(difficulty, acc_req,
                                  lat.sys.acc_margin_robust, tier_ok=tier_ok)

    def per_task(feas_i):
        u = prob.poles[:, None, :] * prob.u_dev        # (P, 1, K)
        vals = jnp.where(feas_i[None], b2[None] * (1.0 + u), BIG)  # (P, F, K)
        rec = vals.min(axis=-1)                         # (P, F)
        worst = rec.max(axis=0)                         # (F,)
        fs_ok = feas_i.any(axis=-1)
        obj = jnp.where(fs_ok, c1 + worst, BIG)
        y = obj.argmin()
        return y, obj[y]

    y, obj = jax.vmap(per_task)(feas_f)
    return y, obj


def total_cost(prob: RobustProblem, sol, difficulty, acc_req, u=None):
    """Realized cost of a solution under deviation u ((K,) or None=nominal)."""
    return prob.lat.solution_cost(sol, u=u)
