"""Plain reference of the R2E-VID decide path, written from the paper's
equations and independent of the program under test.

One round, per stream i (paper §3.2-3.3, Alg. 1-2):

1. temporal gate (Eq. 5-6): a gated recurrent cell over the segment's motion
   features dx, with the forget gate opened by the variance of the last T
   feature vectors; tau = sigmoid(W_o h + b_o);
2. Stage 1 (Alg. 1): smallest edge resolution whose v1 accuracy at the top
   frame rate meets A^q; route to the cloud when tau > tau_cloud or no edge
   resolution is feasible; the route may flip only where
   delta0 + delta1 * |tau_t - tau_{t-1}| >= 1 (temporal consistency);
3. Stage 2 (Alg. 2): column-and-constraint generation over the Gamma-budget
   poles, warm-started from Stage 1's (route, r, top fps), one task at a
   time; the same consistency rule then overrides the CCG route;
4. C6: while the round's bandwidth exceeds the budget, demote (fps first,
   then resolution) the tasks with the largest reclaimable bandwidth that
   stay feasible, for at most ``repair_rounds`` passes.

Nothing here imports the program.  The cost and accuracy surfaces are the
paper's §4.1 model (resolutions, frame rates, K versions, bandwidths,
powers, beta), re-derived from the configuration file's numbers.
"""
from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BIG = 1e9
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# the deployment's tables
# ---------------------------------------------------------------------------
def _pixels(res_p: int) -> int:
    return (res_p * 16 // 9) * res_p


class Tables:
    """Flat option tables, option y = (route * N + r) * Z + p."""

    def __init__(self, sysc: dict):
        res = [int(x) for x in sysc["resolutions"]]
        fps = np.asarray(sysc["fps_options"], np.float32)
        k_n = int(sysc["num_versions"])
        self.n, self.z, self.k = len(res), len(fps), k_n
        self.f = 2 * self.n * self.z
        self.margin = float(sysc["acc_margin_robust"])
        self.total_bw = float(sysc["total_bw_mbps"])
        pix = np.asarray([_pixels(r) for r in res], np.float32)
        data = pix[:, None] * fps[None, :] * np.float32(sysc["segment_sec"]) \
            * np.float32(sysc["bits_per_pixel"]) / np.float32(1e6)      # (N, Z)
        link = np.asarray([sysc["edge_bw_mbps"], sysc["cloud_bw_mbps"]],
                          np.float32)
        t_tx = data[..., None] / link                                   # (N, Z, 2)
        c1 = t_tx + np.float32(sysc["beta"]) * (
            np.float32(sysc["transmit_power_w"]) * t_tx)
        gf = np.zeros((self.n, k_n, 2), np.float32)
        for i, r in enumerate(res):
            for k in range(k_n):
                for t in range(2):
                    g = sysc["v1_gflops_per_frame"] * sysc["version_scale"] ** k
                    if t == 1:
                        g *= sysc["cloud_model_factor"]
                    gf[i, k, t] = g * _pixels(r) / _pixels(1080)
        thr = np.asarray([sysc["edge_gflops"], sysc["cloud_gflops"]], np.float32)
        power = np.asarray([sysc["edge_power_w"], sysc["cloud_power_w"]],
                           np.float32)
        t_cmp = gf[:, None] * fps[None, :, None, None] \
            * np.float32(sysc["segment_sec"]) / thr                     # (N, Z, K, 2)
        b2 = t_cmp + np.float32(sysc["beta"]) * (power * t_cmp)
        # route-major flat layout
        self.c1 = jnp.asarray(np.moveaxis(c1, -1, 0).reshape(self.f))
        self.b2 = jnp.asarray(np.moveaxis(b2, -1, 0).reshape(self.f, k_n))
        self.bw_nz = jnp.asarray(data.reshape(-1))        # both routes draw alike
        ys = np.arange(self.f)
        self.route_of = ys // (self.n * self.z)
        self.r_of = (ys % (self.n * self.z)) // self.z
        self.p_of = ys % self.z
        self.rn = jnp.asarray(res, jnp.float32) / 1080.0
        self.pn = jnp.asarray(fps) / 50.0
        kk = np.arange(k_n, dtype=np.float32)
        self.u_dev = jnp.asarray(sysc["u_dev"] * (0.6 + 0.4 * kk / (k_n - 1)),
                                 jnp.float32)
        gamma = int(sysc["gamma"])
        poles = [s for s in itertools.product((0, 1), repeat=k_n)
                 if sum(s) <= gamma]
        # the same pole order as enumerating bitmasks 0 .. 2^K - 1
        poles.sort(key=lambda s: sum(b << i for i, b in enumerate(s)))
        self.poles = jnp.asarray(poles, jnp.float32)      # (P, K)


def accuracy(z, rn, pn, k, tier):
    """Accuracy surface f(r, p, v, tier | z) of the §4.1 model."""
    a_max = 0.60 + 0.045 * k + 0.04 * tier
    sat = 1.0 - jnp.exp(-(2.5 + 0.3 * k) * rn)
    f = a_max * sat
    f = f - 0.10 * z * (1.0 - pn) - 0.06 * z * (1.0 - rn)
    return jnp.clip(f, 0.0, 1.0)


# ---------------------------------------------------------------------------
# gate
# ---------------------------------------------------------------------------
def gate_cell(p, h, dx, vol, mm):
    """Eq. 5-6 for a stream batch; ``mm`` is the matrix product used."""
    g = jax.nn.sigmoid(mm(dx, p["w_g"]) + mm(h, p["u_g"]) + p["b_g"]
                       + (p["alpha"] * vol)[:, None])
    r = jax.nn.sigmoid(mm(dx, p["w_r"]) + mm(h, p["u_r"]) + p["b_r"])
    cand = jnp.tanh(mm(dx, p["w_h"]) + mm(r * h, p["u_h"]) + p["b_h"])
    h = (1.0 - g) * h + g * cand
    tau = jax.nn.sigmoid(mm(h, p["w_o"]) + p["b_o"])[:, 0]
    return h, tau


def mm_highest(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


def mm_high(a, b):
    """The control's product: float32 at ``Precision.HIGH`` (three bf16
    passes) on a TPU; spelled out as :func:`mm_bf16x3` on backends that
    compute every float32 product exactly."""
    if jax.default_backend() == "tpu":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGH)
    return mm_bf16x3(a, b)


def mm_bf16x3(a, b):
    """The product in three bf16 passes (hi*hi + hi*lo + lo*hi, f32
    accumulation), written out."""
    def split(x):
        hi = x.astype(jnp.bfloat16)
        lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi, lo

    ah, al = split(a)
    bh, bl = split(b)
    dot = lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


#: a consistency check whose delta0 + delta1 * |dtau| lies this close to 1
#: is a tie: float rounding in any implementation may settle it either way
TIE = 1e-5


@partial(jax.jit, static_argnames=("var_window", "delta0", "delta1", "mm"))
def gate_history(params, dx_bank, round_bank_idx, *, var_window, delta0,
                 delta1, mm=mm_highest):
    """Run the gate over every round the program served.

    dx_bank: (B, M, d); round_bank_idx: (R,) bank row of each served round,
    in order.  Returns (tau, last_flip, last_tie), each (R, M): the gate
    score of every round; for every round the index of the latest round <=
    it at which a route flip was allowed (round 0 always is: no history);
    and the latest round <= it whose consistency check was a tie (-1: none).
    """
    m, d = dx_bank.shape[1], dx_bank.shape[2]
    hid = params["u_g"].shape[0]

    def body(carry, xs):
        h, ring, prev_tau, last, tie = carry
        k, b = xs
        dx = dx_bank[b]
        ring = jnp.concatenate([ring[1:], dx[None]], axis=0)   # last T vectors
        vol = jnp.var(ring, axis=0).mean(axis=-1)              # (M,)
        h, tau = gate_cell(params, h, dx, vol, mm)
        move = jnp.abs(tau - prev_tau) * delta1 + delta0
        allowed = (k == 0) | (move >= 1.0)
        last = jnp.where(allowed, k, last)
        tie = jnp.where((k > 0) & (jnp.abs(move - 1.0) < TIE), k, tie)
        return (h, ring, tau, last, tie), (tau, last, tie)

    init = (jnp.zeros((m, hid), jnp.float32),
            jnp.zeros((var_window, m, d), jnp.float32),
            jnp.zeros((m,), jnp.float32), jnp.zeros((m,), jnp.int32),
            -jnp.ones((m,), jnp.int32))
    r = round_bank_idx.shape[0]
    _, (tau, last, tie) = jax.lax.scan(
        body, init, (jnp.arange(r, dtype=jnp.int32), round_bank_idx))
    return tau, last, tie


# ---------------------------------------------------------------------------
# Stage 1, CCG, consistency, C6
# ---------------------------------------------------------------------------
def stage1(tb: Tables, tau, z, aq, tau_cloud):
    """Alg. 1 before consistency: (raw route, r index)."""
    f = accuracy(z[:, None], tb.rn[None], tb.pn[-1], 0.0, 0.0)      # (M, N)
    ok = f >= aq[:, None]
    any_ok = ok.any(axis=1)
    r_idx = jnp.where(any_ok, jnp.argmax(ok, axis=1), tb.n - 1)
    route = jnp.where(any_ok, (tau > tau_cloud).astype(jnp.int32), 1)
    return route, r_idx


def keep_history(route, prev_route, allowed):
    """Temporal consistency: a forbidden flip keeps the previous route."""
    return jnp.where((route != prev_route) & ~allowed & (prev_route >= 0),
                     prev_route, route)


def ccg(tb: Tables, z, aq, warm_y, max_iters: int = 8, theta: float = 1e-4):
    """Alg. 2 per task: master argmin over generated poles, exact SP over the
    poles, incumbent kept; then v* at the chosen y's worst pole, and the
    max-accuracy fallback for tasks with no robustly feasible option."""
    route = jnp.asarray(tb.route_of, jnp.float32)
    rn = tb.rn[tb.r_of]
    pn = tb.pn[tb.p_of]
    ks = jnp.arange(tb.k, dtype=jnp.float32)
    acc = accuracy(z[:, None, None], rn[None, :, None], pn[None, :, None],
                   ks[None, None, :], route[None, :, None])           # (M, F, K)
    feas = acc >= (aq + tb.margin)[:, None, None]
    u = tb.poles * tb.u_dev                                           # (P, K)
    cost = tb.b2[None] * (1.0 + u[:, None, :])                        # (P, F, K)

    def per_task(feas_i, warm):
        rec = jnp.where(feas_i[None], cost, BIG).min(axis=-1)         # (P, F)
        fs_ok = feas_i.any(axis=-1)
        use_warm = (warm >= 0) & fs_ok[jnp.maximum(warm, 0)]
        wy = jnp.maximum(warm, 0)
        warm_pole = rec[:, wy].argmax()
        scen = jnp.zeros((rec.shape[0],)).at[warm_pole].set(
            jnp.where(use_warm, 1.0, 0.0))
        o_up = jnp.where(use_warm, tb.c1[wy] + rec[warm_pole, wy], BIG)

        def body(c):
            it, scen, o_up, best, _ = c
            eta = jnp.where(scen.sum() > 0,
                            jnp.where(scen[:, None] > 0, rec, -BIG).max(axis=0),
                            0.0)
            obj = jnp.where(fs_ok, tb.c1 + eta, BIG)
            y = obj.argmin()
            o_down = obj[y]
            worst = rec[:, y].argmax()
            q = rec[worst, y]
            best = jnp.where(tb.c1[y] + q < o_up, y, best)
            o_up = jnp.minimum(o_up, tb.c1[y] + q)
            return it + 1, scen.at[worst].set(1.0), o_up, best, \
                (o_up - o_down) <= theta

        _, _, _, y, _ = jax.lax.while_loop(
            lambda c: (c[0] < max_iters) & ~c[4], body,
            (0, scen, o_up, wy, jnp.asarray(False)))
        worst = rec[:, y].argmax()
        vals = jnp.where(feas_i[y], tb.b2[y] * (1.0 + u[worst]), BIG)
        return y, vals.argmin()

    y, v = jax.vmap(per_task)(feas, warm_y)
    none_ok = ~feas.any(axis=(1, 2))
    best = acc.reshape(acc.shape[0], -1).argmax(axis=1)
    y = jnp.where(none_ok, best // tb.k, y)
    v = jnp.where(none_ok, best % tb.k, v)
    return (jnp.asarray(tb.route_of)[y], jnp.asarray(tb.r_of)[y],
            jnp.asarray(tb.p_of)[y], v)


def c6_repair(tb: Tables, route, r, p, v, z, aq, budget, passes: int):
    """Top-k demotion passes until the round's draw fits the budget."""
    thr = aq + tb.margin
    vf = v.astype(jnp.float32)
    tf = route.astype(jnp.float32)
    bw_of = lambda ri, pi: tb.bw_nz[ri * tb.z + pi]
    active = jnp.asarray(True)
    for _ in range(passes):
        excess = bw_of(r, p).sum() - budget
        go = active & (excess > 0)
        p_dn = jnp.maximum(p - 1, 0)
        r_dn = jnp.maximum(r - 1, 0)
        can_p = (p > 0) & (accuracy(z, tb.rn[r], tb.pn[p_dn], vf, tf) >= thr)
        can_r = (r > 0) & (accuracy(z, tb.rn[r_dn], tb.pn[p], vf, tf) >= thr)
        gain = jnp.where(can_p, bw_of(r, p) - bw_of(r, p_dn),
                         jnp.where(can_r, bw_of(r, p) - bw_of(r_dn, p), -BIG))
        order = jnp.argsort(-gain)
        g = gain[order]
        before = jnp.concatenate([jnp.zeros((1,), g.dtype), jnp.cumsum(g)[:-1]])
        pick = jnp.zeros(r.shape, bool).at[order].set((before < excess) & (g > 0))
        pick = pick & go
        r = jnp.where(pick & ~can_p, r_dn, r)
        p = jnp.where(pick & can_p, p_dn, p)
        active = go & pick.any()
    return r, p


@partial(jax.jit, static_argnames=("tb", "tau_cloud", "delta0", "delta1",
                                   "passes"))
def decide_round(tb: Tables, tau, tau_prev, z, aq, prev_route, budget,
                 settled, *, tau_cloud, delta0, delta1, passes):
    """One round's decisions given the gate scores and the previous route.
    ``settled`` (M,): where >= 0, the route a tie in the stream's
    consistency checks settled on, which C6 then plans with."""
    allowed = (prev_route < 0) | (
        (jnp.abs(tau - tau_prev) * delta1 + delta0) >= 1.0)
    raw, r_idx = stage1(tb, tau, z, aq, tau_cloud)
    warm_route = keep_history(raw, prev_route, allowed)
    warm_y = (warm_route * tb.n + r_idx) * tb.z + (tb.z - 1)
    route, r, p, v = ccg(tb, z, aq, warm_y.astype(jnp.int32))
    route = keep_history(route, prev_route, allowed)
    route = jnp.where(settled >= 0, settled, route)
    r, p = c6_repair(tb, route, r, p, v, z, aq, budget, passes)
    return route, r, p, v


Tables.__hash__ = object.__hash__     # static under jit: one per process
