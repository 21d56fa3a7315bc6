"""Driver for router deployments: the decide path a deployment calls every
round, ``ServeSession.step`` on a gate-mode r2evid policy with the Pallas
routing kernels (``force="auto"``), closed loop, rounds back to back.

A round's clock starts when its host arrays (features, difficulty, floors,
telemetry) are handed to the session and stops when its decisions and gate
scores (route, r, p, v, tau) are on the host.

The check replays every round the session served through the plain
reference (``bench/ref/router_ref.py``): the gate over all of them, then
Stage 1, CCG, consistency and C6 on a sample of window rounds drawn from the
seed as the window runs (``check_rounds`` per telemetry state), and compares
every camera's decisions (exactly) and gate score (by the widest and the
root-mean-square gap) in those rounds.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time

import numpy as np

import traffic_gen
from record import Record
from seeds import jax_key, np_rng


def gate_weights(seed: int, d: int, m: int):
    """The gate's parameters from the seed, on the device, in one jitted
    call: normal(0, 1/sqrt(fan_in)) weights, zero biases, alpha = 1."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        k = jax.random.split(key, 7)
        nrm = lambda i, shape, fan: (
            jax.random.normal(k[i], shape, jnp.float32) * fan ** -0.5)
        zeros = lambda n: jnp.zeros((n,), jnp.float32)
        return {"w_g": nrm(0, (d, m), d), "u_g": nrm(1, (m, m), m),
                "b_g": zeros(m), "alpha": jnp.ones((), jnp.float32),
                "w_r": nrm(2, (d, m), d), "u_r": nrm(3, (m, m), m),
                "b_r": zeros(m),
                "w_h": nrm(4, (d, m), d), "u_h": nrm(5, (m, m), m),
                "b_h": zeros(m),
                "w_o": nrm(6, (m, 1), m), "b_o": zeros(1)}

    return make(jax_key(seed, "gate.weights"))


def system_config(cfg: dict):
    from repro.core.cost_model import SystemConfig

    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in cfg["deployment"].items()}
    return SystemConfig(**fields)


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, *, counter):
        from repro.core.features import feature_dim
        from repro.core.gating import GateConfig
        from repro.core.router import RouterConfig
        from repro.serving.policy import make_policy
        from repro.serving.session import ServeSession

        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.m = int(cfg["cameras"])
        self.d = feature_dim()
        gate = cfg["gate"]
        gcfg = GateConfig(d_feature=self.d, d_hidden=gate["d_hidden"],
                          var_window=gate["var_window"])
        rcfg = RouterConfig(**cfg["router"])
        params = gate_weights(seed, self.d, gate["d_hidden"])
        policy = make_policy("r2evid", system_config(cfg), gate_cfg=gcfg,
                             gate_params=params, rcfg=rcfg)
        self.session = ServeSession(policy, self.m, force="auto")
        self.bank = traffic_gen.round_bank(mix, self.m, self.d, seed)
        self.k = 0                       # rounds served so far, warm-up too
        self._rng = np_rng(seed, "check.rounds")
        self._seen: dict[str, int] = {}
        self._kept: dict[str, list] = {}
        # warm up on this cell's shapes until a round compiles nothing
        for _ in range(int(mix.get("max_warmup_rounds", 8))):
            before = counter.total()
            self._round(lambda name: contextlib.nullcontext())
            if self.k >= 2 and counter.total() == before:
                break

    # -- one round ----------------------------------------------------------
    def _obs(self, k: int):
        from repro.serving.policy import Observation

        b = k % self.bank.rounds
        bws = None if self.bank.bw_scale is None else self.bank.bw_scale[b]
        return Observation(z=self.bank.z[b], aq=self.bank.aq[b],
                           dx=self.bank.dx[b], bw_scale=bws)

    def _round(self, span):
        import jax

        with span("bench.round"):
            with span("bench.route"):
                sol = self.session.step(self._obs(self.k))
            with span("bench.fetch"):
                out = jax.device_get((sol["route"], sol["r"], sol["p"],
                                      sol["v"], sol["tau"]))
        self.k += 1
        return out

    # -- the measured window --------------------------------------------------
    def window(self, seconds: float, span) -> Record:
        round_s = []
        t0 = time.perf_counter()
        end = t0
        while end - t0 < seconds:
            start = time.perf_counter()
            out = self._round(span)
            end = time.perf_counter()
            round_s.append(end - start)
            self._sample(self.k - 1, out)
        n = len(round_s)
        return Record(window_s=end - t0, attempted=n * self.m, failed=0,
                      rounds=n, round_s=round_s,
                      extra={"cameras": self.m})

    def _group(self, k: int) -> str:
        if self.bank.bw_scale is None:
            return "any"
        return "bad" if self.bank.bw_scale[k % self.bank.rounds] < 1.0 \
            else "good"

    def _sample(self, k: int, out):
        """Keep a uniform sample of the window's rounds per telemetry state
        (reservoir sampling from the seed): ``check_rounds`` of each."""
        g = self._group(k)
        want = int(self.mix["check_rounds"][g])
        seen = self._seen.get(g, 0)
        self._seen[g] = seen + 1
        kept = self._kept.setdefault(g, [])
        slot = seen if seen < want else int(self._rng.integers(0, seen + 1))
        if slot < want:
            row = (k, np.stack(out[:4]).astype(np.int8), np.array(out[4]))
            if slot < len(kept):
                kept[slot] = row
            else:
                kept.append(row)

    def free(self):
        """Drop the program's state before the reference runs."""
        self.session = None
        gc.collect()

    # -- comparison with the plain reference ----------------------------------
    def sampled(self):
        """The checked rounds in order: [(round, decisions (4, M), tau)]."""
        return sorted((row for rows in self._kept.values() for row in rows),
                      key=lambda row: row[0])

    def check(self, rec: Record) -> dict:
        rows = self.sampled()
        picked = [k for k, _, _ in rows]
        lim = self.cfg["limits"]
        if not rows:
            return {"rounds_unchecked": {"value": 1, "limit": 0}}
        got_dec = np.stack([d for _, d, _ in rows]).astype(np.int32)
        got_tau = np.stack([t for _, _, t in rows])
        want_dec, want_tau, ties = reference(
            self.cfg, self.bank, self.seed, self.k, picked, got_dec[:, 0])
        return compare(got_dec, got_tau, want_dec, want_tau, ties, lim)


def compare(got_dec, got_tau, want_dec, want_tau, ties, lim) -> dict:
    """The compared numbers: (round, camera) pairs whose route, r, p or v
    differ, the widest gate-score gap and the root-mean-square gate-score
    gap.  Ties are reported, not compared (their route is the program's,
    see :func:`reference`)."""
    diff = (got_tau - want_tau).astype(np.float64)
    print(f"bench: {int(ties.sum())} of {ties.size} checked decisions rest "
          f"on a consistency tie", file=sys.stderr)
    n_bad = int((got_dec != want_dec).any(axis=1).sum())
    got = {"decisions_differ": n_bad,
           "tau_gap": float(np.abs(diff).max()),
           "tau_rms_gap": float(np.sqrt((diff ** 2).mean()))}
    return {k: {"value": v, "limit": lim[k]} for k, v in got.items()}


def control(cell, rec: Record) -> dict:
    """The control's compared numbers: the reference with the gate's
    products at ``Precision.HIGH`` (three bf16 passes, the step below the
    configured float32 at ``HIGHEST``) put in the program's place."""
    from ref import router_ref as ref

    picked = [k for k, _, _ in cell.sampled()]
    args = (cell.cfg, cell.bank, cell.seed, cell.k, picked)
    want_dec, want_tau, ties = reference(*args)
    got_dec, got_tau, _ = reference(*args, mm=ref.mm_high)
    return compare(got_dec, got_tau, want_dec, want_tau, ties,
                   cell.cfg["limits"])


def reference(cfg: dict, bank, seed: int, n_rounds: int, picked,
              got_route=None, mm=None):
    """The plain reference's (decisions (len(picked), 4, M), tau
    (len(picked), M), ties (len(picked), M)) for the picked rounds of a
    session that served ``n_rounds`` rounds of ``bank`` in order (round k
    used bank row k mod B).

    A stream whose route at a picked round rests on a tie of a consistency
    check (``router_ref.TIE``) since its last allowed flip is marked in
    ``ties``; there the route the program served (``got_route``, the
    picked rounds' (len(picked), M) routes) is taken, and C6 plans with it.
    ``mm`` replaces the gate's matrix product (the control)."""
    import jax.numpy as jnp

    from ref import router_ref as ref

    gate, router = cfg["gate"], cfg["router"]
    tb = ref.Tables(cfg["deployment"])
    d = bank.dx.shape[2]
    params = gate_weights(seed, d, gate["d_hidden"])
    dx_bank = jnp.asarray(bank.dx)
    rows = jnp.asarray(np.arange(n_rounds) % bank.rounds, jnp.int32)
    tau, last, tie = ref.gate_history(
        params, dx_bank, rows, var_window=gate["var_window"],
        delta0=float(router["delta0"]), delta1=float(router["delta1"]),
        mm=mm or ref.mm_highest)
    del dx_bank
    z = jnp.asarray(bank.z)
    aq = jnp.asarray(bank.aq)
    m = bank.z.shape[1]
    lane = jnp.arange(m)
    total = jnp.float32(cfg["deployment"]["total_bw_mbps"])
    kw = dict(tau_cloud=float(router["tau_cloud"]),
              delta0=float(router["delta0"]), delta1=float(router["delta1"]),
              passes=int(router["repair_rounds"]))
    none = -jnp.ones((m,), jnp.int32)
    big = jnp.float32(3e38)              # no C6: only the route is read
    decs, taus, ties = [], [], []
    for i, t in enumerate(picked):
        # the previous route: the CCG route of the latest round <= t-1 at
        # which a flip was allowed (consistency held it since)
        if t > 0:
            s = last[t - 1]
            sb = s % bank.rounds
            prev, _, _, _ = ref.decide_round(
                tb, tau[s, lane], tau[s, lane], z[sb, lane], aq[sb, lane],
                none, big, none, **kw)
            tied = tie[t] >= s
        else:
            prev = none
            tied = jnp.zeros((m,), bool)
        settled = none if got_route is None else jnp.where(
            tied, jnp.asarray(got_route[i], jnp.int32), -1)
        b = t % bank.rounds
        budget = total if bank.bw_scale is None \
            else total * jnp.float32(bank.bw_scale[b])
        out = ref.decide_round(tb, tau[t], tau[t - 1] if t > 0 else
                               jnp.zeros_like(tau[t]), z[b], aq[b], prev,
                               budget, settled, **kw)
        decs.append(np.stack([np.asarray(x) for x in out]))
        taus.append(np.asarray(tau[t]))
        ties.append(np.asarray(tied))
    return np.stack(decs).astype(np.int32), np.stack(taus), np.stack(ties)
