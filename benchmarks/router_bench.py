"""Router engine benchmark: steady-state ``route_step`` latency, the fused
scan drivers, the CCG sweep, and simulator realization throughput.

  PYTHONPATH=src python benchmarks/router_bench.py [--streams 64] [--steps 50]
  PYTHONPATH=src python benchmarks/router_bench.py --json   # + BENCH_router.json
  PYTHONPATH=src python benchmarks/router_bench.py --check BENCH_router.json

Prints ``name,us_per_call,derived`` CSV lines (the repo benchmark contract):

  router/route_step      — steady-state latency of one jit-compiled streaming
                           step (fused gate + warm-started CCG + C6 repair)
                           and the derived segments/sec
  router/route_scan_per_segment — amortized per-segment cost when a whole
                           multi-segment round runs under one lax.scan
  router/solve_ccg       — the unrolled masked CCG sweep alone
  router/solve_ccg_while — the legacy per-task while_loop CCG (the unrolled
                           solver's oracle), plus the unrolled speedup
  router/route_windowed  — the stateless windowed ``route`` on the same load
                           (re-scans the whole feature window each call)
  engine/serve_scan_per_round — whole-run driver (route + realize per round,
                           all rounds in one compiled scan)
  sim/realize_vectorized — jnp ``Simulator.realize`` path
  sim/realize_reference  — original per-task loop, plus max metric deviation
                           between the two on a fixed seed
  sim/realize_batch_per_round — amortized per-round cost when whole rounds
                           are realized in one vmapped batch
  policy/{name}          — every registered policy (a2_cloud_only, jcab,
                           rdap, sniper, r2evid) through the SAME compiled
                           ``ServeSession.run`` scan: µs per routed+realized
                           round at the default M, so baseline and R2E-VID
                           numbers are apples-to-apples compiled programs
  policy/{name}@{scenario} — the same compiled serve run through a named
                           adverse scenario (``repro.serving.scenarios``):
                           availability masks, bandwidth traces, and hedged
                           realization fused into the one scan, so the
                           scenario engine's compiled overhead is a gated
                           number, not a hope (all policies x edge_outage /
                           bw_collapse, r2evid x the rest of the suite)
  sweep/{stage}@M{m}     — ``--streams-sweep`` rows: per-stage latency (gate,
                           stage1, ccg, repair, realize, and the full
                           route_step) at each stream count M, with
                           us_per_segment derived so batch amortization —
                           and the LPT-packing realization wall — is
                           measured, not assumed
  sweep/route_step_sharded@M{m} / sweep/route_step_hier@M{m}
                         — ``--sharded-sweep`` rows: the whole compiled
                           sharded serve round on a mesh over every device
                           of this one process (``jax.devices()``), gathered
                           tail vs the hierarchical O(n_devices) tail
                           (``vs_gathered`` in the hier rows' derived field).
                           A CPU rehearsal gets virtual devices from the
                           shell that starts it:
                           ``XLA_FLAGS=--xla_force_host_platform_device_count=8``

With ``--json`` the same rows are written to ``BENCH_router.json`` so every
PR records the perf trajectory (CI uploads it as an artifact), and a
one-line snapshot (commit, date, device, headline router/ and sweep rows)
is appended to ``BENCH_history.jsonl`` — the append-only per-PR perf log
that survives baseline refreshes overwriting the JSON.  With
``--check PATH`` the run becomes a regression gate: any benchmark more than
``REGRESSION_FACTOR``x slower than the same-named row in the checked-in
baseline fails the process (loose threshold — shared runners are noisy and
CI runs tiny smoke sizes against the full-size baseline).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.jax_env import device_info, enable_compile_cache

# --check fails any benchmark this much slower than its baseline row
REGRESSION_FACTOR = 2.0


def _timeit(fn, iters: int, chunks: int = 3) -> float:
    """Best-of-``chunks`` mean latency in µs — the min over chunks is the
    standard noise-robust estimator on shared machines."""
    fn()  # warm-up / compile
    per_chunk = max(iters // chunks, 1)
    best = float("inf")
    for _ in range(chunks):
        t0 = time.perf_counter()
        for _ in range(per_chunk):
            fn()
        best = min(best, (time.perf_counter() - t0) / per_chunk)
    return best * 1e6  # us


def bench_route_step(streams: int, steps: int, window: int = 8,
                     scan_segments: int = 16):
    from repro.core.cost_model import SystemConfig
    from repro.core.features import feature_dim
    from repro.core.gating import GateConfig, gate_specs
    from repro.core.robust import (RobustProblem, solve_ccg, solve_ccg_fused,
                                   solve_ccg_while)
    from repro.core.router import RouterEngine, route
    from repro.models.params import init_params

    sys_ = SystemConfig()
    prob = RobustProblem.build(sys_)
    gcfg = GateConfig(d_feature=feature_dim())
    gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.uniform(0, 1, streams), jnp.float32)
    aq = jnp.asarray(rng.uniform(0.5, 0.75, streams), jnp.float32)
    dx = jnp.asarray(rng.normal(size=(streams, feature_dim())), jnp.float32)

    engine = RouterEngine(prob, gcfg, gparams, n_streams=streams)

    def step():
        sol = engine.step(dx, z, aq)
        jax.block_until_ready(sol["route"])

    us_step = _timeit(step, steps)
    seg_per_s = streams / (us_step / 1e6)

    dx_seq = jnp.asarray(
        rng.normal(size=(scan_segments, streams, feature_dim())), jnp.float32)

    def scan_round():
        sols = engine.step_many(dx_seq, z, aq)
        jax.block_until_ready(sols["route"])

    us_scan = _timeit(scan_round, max(steps // 4, 3)) / scan_segments
    scan_seg_per_s = streams / (us_scan / 1e6)

    def ccg_fused():
        sol = solve_ccg_fused(prob, z, aq)
        jax.block_until_ready(sol["route"])

    us_ccg_fused = _timeit(ccg_fused, steps)

    def ccg():
        sol = solve_ccg(prob, z, aq)
        jax.block_until_ready(sol["route"])

    us_ccg = _timeit(ccg, steps)

    def ccg_while():
        sol = solve_ccg_while(prob, z, aq)
        jax.block_until_ready(sol["route"])

    us_ccg_while = _timeit(ccg_while, steps)

    dx_win = jnp.asarray(rng.normal(size=(streams, window, feature_dim())), jnp.float32)

    def windowed():
        sol = route(prob, gcfg, gparams, dx_win, z, aq)
        jax.block_until_ready(sol["route"])

    us_win = _timeit(windowed, max(steps // 4, 3))
    return [
        ("router/route_step", us_step, f"segments_per_s={seg_per_s:.0f}"),
        ("router/route_scan_per_segment", us_scan,
         f"segments_per_s={scan_seg_per_s:.0f},scan_len={scan_segments}"),
        ("router/solve_ccg_fused", us_ccg_fused,
         f"tasks={streams},vs_unrolled={us_ccg / max(us_ccg_fused, 1e-9):.2f}x"),
        ("router/solve_ccg", us_ccg, f"tasks={streams}"),
        ("router/solve_ccg_while", us_ccg_while,
         f"tasks={streams},unrolled_speedup={us_ccg_while / max(us_ccg, 1e-9):.2f}x"),
        ("router/route_windowed", us_win, f"window={window}"),
    ]


def bench_policies(streams: int, rounds: int, iters: int = 5):
    """Every registered policy through the one compiled ``ServeSession.run``
    scan — the apples-to-apples serving comparison the paper's claims rest
    on (baselines get batching + donation + the fused realization exactly
    like R2E-VID).  µs per routed+realized round."""
    from repro.core.cost_model import SystemConfig
    from repro.core.features import feature_dim
    from repro.core.gating import GateConfig, gate_specs
    from repro.models.params import init_params
    from repro.serving.policy import POLICIES, make_policy
    from repro.serving.session import ServeSession
    from repro.serving.simulator import SimConfig, Simulator

    sys_ = SystemConfig()
    sim = Simulator(sys_, SimConfig(n_tasks=streams, seed=11, bw_fluctuation=0.2))
    stream = sim.sample_stream(n_rounds=rounds, feature_seed=2)
    rows = []
    for name in sorted(POLICIES):
        if name == "r2evid":
            gcfg = GateConfig(d_feature=feature_dim())
            gp = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))
            policy = make_policy(name, sys_, gate_cfg=gcfg, gate_params=gp)
        else:
            policy = make_policy(name, sys_)
        session = ServeSession(policy, n_streams=streams, sim=sim.sim)

        def run():
            mets = session.run(stream)
            jax.block_until_ready(mets["cost"])

        us = _timeit(run, iters) / rounds
        rows.append((f"policy/{name}", us,
                     f"rounds={rounds},streams={streams},us_per_segment="
                     f"{us / streams:.3f}"))
    return rows


def bench_scenarios(streams: int, rounds: int, iters: int = 5,
                    scenarios=("edge_outage", "bw_collapse", "churn")):
    """Degraded serving: every registered policy through the SAME compiled
    ``ServeSession.run`` scan under the named adverse scenarios, plus
    r2evid through the rest of the suite — ``policy/{name}@{scenario}``
    rows with the same per-round-µs contract as ``policy/{name}``, so
    ``--check`` gates the scenario engine's compiled overhead (availability
    masks, bandwidth traces, hedged realization) exactly like the benign
    path."""
    from repro.core.cost_model import SystemConfig
    from repro.serving.policy import POLICIES, make_policy
    from repro.serving.scenarios import (SUITE, apply_scenario,
                                         compile_scenario)
    from repro.serving.session import ServeSession
    from repro.serving.simulator import SimConfig, Simulator

    sys_ = SystemConfig()
    simc = SimConfig(n_tasks=streams, n_rounds=rounds, seed=11,
                     bw_fluctuation=0.2)
    stream = Simulator(sys_, simc).sample_stream(rounds)
    cells = [(p, s) for s in scenarios for p in sorted(POLICIES)]
    cells += [("r2evid", s) for s in SUITE if s not in scenarios]
    rows = []
    for name, scen in cells:
        trace = compile_scenario(scen, sys_, simc, rounds)
        degraded = apply_scenario(stream, trace)
        session = ServeSession(make_policy(name, sys_), streams, sim=simc,
                               hedge=trace.hedge, admission=trace.admission)

        def run(session=session, degraded=degraded):
            mets = session.run(degraded)
            jax.block_until_ready(mets["cost"])

        us = _timeit(run, iters) / rounds
        rows.append((f"policy/{name}@{scen}", us,
                     f"rounds={rounds},streams={streams}"))
    return rows


def bench_streams_sweep(sweep, steps: int):
    """Stream-count scaling of the table-free hot path: per-stage µs at each
    M plus the full ``route_step``.  The per-segment µs in ``derived`` is the
    checked-in evidence that large-M batches amortize (sub-linear scaling):
    ``per_seg_vs_M{m0}`` is the ratio of this row's µs/segment to the
    smallest-M row's — < 1.0 means batching wins.  The ``realize`` stage
    times ``realize_rounds`` (fair-share transmission + LPT queueing +
    pointwise accuracy) on one M-task round — the ROADMAP's suspected next
    scaling wall is its sequential O(M) packing scan, so its per-segment
    µs is the number to watch."""
    from repro.core.cost_model import SystemConfig
    from repro.core.features import feature_dim
    from repro.core.gating import GateConfig, gate_specs, gate_step_batch, init_batch_state
    from repro.core.robust import RobustProblem, solve_ccg_fused
    from repro.core.router import (
        RouterEngine,
        enforce_bandwidth,
        stage1_configure,
    )
    from repro.models.params import init_params
    from repro.serving.simulator import realize_rounds

    sys_ = SystemConfig()
    prob = RobustProblem.build(sys_)
    gcfg = GateConfig(d_feature=feature_dim())
    gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))

    gate_j = jax.jit(lambda st, dx: gate_step_batch(gcfg, gparams, st, dx))
    stage1_j = jax.jit(
        lambda taus, z, aq, pr, pt: stage1_configure(sys_, taus, z, aq, pr, pt))
    repair_j = jax.jit(
        lambda sol, z, aq: enforce_bandwidth(prob.lat, sol, z, aq))

    rows = []
    base_per_seg = {}
    m0 = sweep[0]
    for m in sweep:
        rng = np.random.default_rng(m)
        z = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
        aq = jnp.asarray(rng.uniform(0.5, 0.75, m), jnp.float32)
        dx = jnp.asarray(rng.normal(size=(m, feature_dim())), jnp.float32)
        taus = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
        prev_r = -jnp.ones((m,), jnp.int32)
        prev_t = jnp.zeros((m,), jnp.float32)
        # floor of 10: the cheap stages (stage1/realize, ~100-300 µs) are
        # dispatch-noise-dominated; the CI smoke's tiny --steps would give
        # best-of-1-call chunks and flake the --check gate
        iters = max(steps // 3, 10)

        gate_st = init_batch_state(gcfg, m)

        def bench_gate():
            st, (tau, _) = gate_j(gate_st, dx)
            jax.block_until_ready(tau)

        def bench_stage1():
            route, r = stage1_j(taus, z, aq, prev_r, prev_t)
            jax.block_until_ready(route)

        def bench_ccg():
            sol = solve_ccg_fused(prob, z, aq)
            jax.block_until_ready(sol["route"])

        sol0 = solve_ccg_fused(prob, z, aq)
        sol_fixed = {k: sol0[k] for k in ("route", "r", "p", "v")}

        def bench_repair():
            fixed, _ = repair_j(sol_fixed, z, aq)
            jax.block_until_ready(fixed["r"])

        bwm = jnp.asarray(rng.uniform(0.8, 1.0, 2), jnp.float32)
        u_real = jnp.asarray(rng.uniform(0, 0.3, sys_.num_versions), jnp.float32)

        def bench_realize_round():
            met = realize_rounds(
                sys_, z, bwm, u_real, sol_fixed["route"], sol_fixed["r"],
                sol_fixed["p"], sol_fixed["v"], n_edge=4, n_cloud=1)
            jax.block_until_ready(met["cost"])

        engine = RouterEngine(prob, gcfg, gparams, n_streams=m)

        def bench_step():
            sol = engine.step(dx, z, aq)
            jax.block_until_ready(sol["route"])

        stages = [("gate", bench_gate), ("stage1", bench_stage1),
                  ("ccg", bench_ccg), ("repair", bench_repair),
                  ("realize", bench_realize_round),
                  ("route_step", bench_step)]
        for stage, fn in stages:
            us = _timeit(fn, iters)
            per_seg = us / m
            derived = f"streams={m},us_per_segment={per_seg:.3f}"
            if stage == "route_step":
                derived += f",segments_per_s={m / (us / 1e6):.0f}"
            if m != m0 and stage in base_per_seg:
                derived += (f",per_seg_vs_M{m0}="
                            f"{per_seg / base_per_seg[stage]:.3f}")
            else:
                base_per_seg[stage] = per_seg
            rows.append((f"sweep/{stage}@M{m}", us, derived))
    return rows


def bench_sharded(sweep, rounds: int, iters: int):
    """One compiled sharded serve scan per (M, tail-mode) cell on a mesh
    over all of this process's devices, gathered vs hierarchical, µs per
    round.  The pools are sized 2/1 servers per device so the hierarchical
    static partition divides evenly at any device count."""
    from repro.core.cost_model import SystemConfig
    from repro.serving.policy import make_policy
    from repro.serving.session import ServeSession
    from repro.serving.simulator import SimConfig, Simulator
    from repro.sharding.compat import make_mesh

    sys_ = SystemConfig()
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",))
    pol = make_policy("r2evid", sys_)
    rows = []
    for m in sweep:
        simc = SimConfig(n_tasks=m, n_rounds=rounds, seed=m,
                         bw_fluctuation=0.2)
        stream = Simulator(sys_, simc).sample_stream(rounds)
        kw = dict(sim=simc, n_edge=2 * n_dev, n_cloud=n_dev)
        sess_g = ServeSession(pol, m, **kw)

        def run_g():
            mets = sess_g.run_sharded(mesh, stream)
            jax.block_until_ready(mets["cost"])

        us_g = _timeit(run_g, iters) / rounds
        sess_h = ServeSession(pol, m, hierarchical=True, **kw)

        def run_h():
            mets = sess_h.run_sharded(mesh, stream)
            jax.block_until_ready(mets["cost"])

        us_h = _timeit(run_h, iters) / rounds
        rows.append((f"sweep/route_step_sharded@M{m}", us_g,
                     f"streams={m},devices={n_dev},"
                     f"us_per_segment={us_g / m:.3f}"))
        rows.append((f"sweep/route_step_hier@M{m}", us_h,
                     f"streams={m},devices={n_dev},"
                     f"us_per_segment={us_h / m:.3f},"
                     f"vs_gathered={us_h / max(us_g, 1e-9):.3f}x"))
    return rows


def bench_serve_scan(streams: int, rounds: int, iters: int = 5):
    from repro.core.cost_model import SystemConfig
    from repro.core.features import feature_dim
    from repro.core.gating import GateConfig, gate_specs
    from repro.core.robust import RobustProblem
    from repro.core.router import init_router_state
    from repro.models.params import init_params
    from repro.serving.scan import serve_scan
    from repro.serving.simulator import SimConfig, Simulator

    sys_ = SystemConfig()
    prob = RobustProblem.build(sys_)
    gcfg = GateConfig(d_feature=feature_dim())
    gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(0))
    sim = Simulator(sys_, SimConfig(n_tasks=streams, seed=5, bw_fluctuation=0.2))
    rnds = [sim.sample_round() for _ in range(rounds)]
    rng = np.random.default_rng(1)
    dx_seq = jnp.asarray(rng.normal(size=(rounds, streams, feature_dim())), jnp.float32)
    z = jnp.asarray(np.stack([r["z"] for r in rnds]), jnp.float32)
    aq = jnp.asarray(np.stack([r["aq"] for r in rnds]), jnp.float32)
    bwm = jnp.asarray(np.stack([r["bw_mult"] for r in rnds]), jnp.float32)
    u = jnp.asarray(np.stack([r["u"] for r in rnds]), jnp.float32)
    # the compiled scan donates its carry, so the state must be threaded
    # (exactly how a real serving loop uses it) rather than reused
    carry = {"state": init_router_state(gcfg, streams)}

    def run():
        carry["state"], mets = serve_scan(
            prob, gcfg, gparams, carry["state"], dx_seq, z, aq, bwm, u,
            n_edge=sim.sim.n_edge_servers, n_cloud=sim.sim.n_cloud_servers)
        jax.block_until_ready(mets["cost"])

    us = _timeit(run, iters) / rounds
    return [("engine/serve_scan_per_round", us,
             f"rounds={rounds},streams={streams}")]


def bench_realize(n_tasks: int, iters: int = 20):
    from repro.core.cost_model import SystemConfig
    from repro.serving.baselines import make_method
    from repro.serving.simulator import SimConfig, Simulator

    sys_ = SystemConfig()
    sim = Simulator(sys_, SimConfig(n_tasks=n_tasks, seed=3, bw_fluctuation=0.2))
    rnd = sim.sample_round()
    cfg = make_method("JCAB", sys_)(rnd, {})

    us_vec = _timeit(lambda: sim.realize(rnd, cfg), iters)
    us_ref = _timeit(lambda: sim.realize_reference(rnd, cfg), iters)

    n_batch = 16
    rnds = [rnd] * n_batch
    cfgs = [cfg] * n_batch
    us_batch = _timeit(lambda: sim.realize_batch(rnds, cfgs), max(iters // 4, 3))
    us_batch_per_round = us_batch / n_batch

    # parity on a fixed seed: identical observation noise for both paths
    noise = np.zeros(n_tasks)
    met_v = sim._realize_deterministic(rnd, cfg)
    met_r = sim.realize_reference(rnd, cfg, noise=noise)
    dev = max(
        float(np.abs(met_v[k] - met_r[k]).max())
        for k in ("delay", "energy", "cost", "accuracy")
    )
    return [
        ("sim/realize_vectorized", us_vec, f"n_tasks={n_tasks}"),
        ("sim/realize_reference", us_ref,
         f"speedup={us_ref / max(us_vec, 1e-9):.1f}x,max_dev={dev:.2e}"),
        ("sim/realize_batch_per_round", us_batch_per_round,
         f"rounds={n_batch},speedup_vs_loop={us_ref / max(us_batch_per_round, 1e-9):.1f}x"),
    ]


def check_regressions(rows, baseline_path: str) -> int:
    """Compare rows against a baseline JSON; return the number of rows more
    than REGRESSION_FACTOR x slower (rows without a baseline entry pass)."""
    base = json.loads(pathlib.Path(baseline_path).read_text())
    base_us = {b["name"]: b["us_per_call"] for b in base["benchmarks"]}
    bad = 0
    for name, us, _ in rows:
        ref = base_us.get(name)
        if ref is None:
            print(f"check: {name} has no baseline row — skipped")
            continue
        ratio = us / max(ref, 1e-9)
        verdict = "REGRESSION" if ratio > REGRESSION_FACTOR else "ok"
        print(f"check: {name} {us:.1f}us vs baseline {ref:.1f}us "
              f"({ratio:.2f}x) {verdict}")
        bad += ratio > REGRESSION_FACTOR
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=64)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--tasks", type=int, default=200)
    ap.add_argument("--scan-rounds", type=int, default=16)
    ap.add_argument("--streams-sweep", default="64,256,512,1024,4096",
                    help="comma-separated stream counts for the per-stage "
                         "large-M scaling rows (empty string disables; 512 "
                         "stays in the default so baseline refreshes keep "
                         "the M=512 rows CI checks against)")
    ap.add_argument("--sharded-sweep", default="256,1024,4096",
                    help="comma-separated stream counts for the sharded "
                         "serve rows on a mesh over jax.devices() (empty "
                         "string disables)")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_router.json next to the repo root")
    ap.add_argument("--check", metavar="BASELINE",
                    help="fail if any benchmark is >%.0fx slower than the "
                         "same-named row in this baseline JSON" % REGRESSION_FACTOR)
    args = ap.parse_args()
    enable_compile_cache()
    device = device_info()
    print(f"device: {device}")

    rows = []
    rows += bench_route_step(args.streams, args.steps)
    rows += bench_serve_scan(args.streams, args.scan_rounds)
    rows += bench_policies(args.streams, args.scan_rounds)
    rows += bench_scenarios(args.streams, args.scan_rounds)
    rows += bench_realize(args.tasks)
    if args.streams_sweep:
        sweep = [int(s) for s in args.streams_sweep.split(",")]
        rows += bench_streams_sweep(sweep, args.steps)
    if args.sharded_sweep:
        sweep = [int(s) for s in args.sharded_sweep.split(",")]
        rows += bench_sharded(sweep, args.scan_rounds,
                              max(args.steps // 6, 3))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    n_bad = check_regressions(rows, args.check) if args.check else 0

    if args.json:
        out = {
            "config": {"streams": args.streams, "steps": args.steps,
                       "tasks": args.tasks, "scan_rounds": args.scan_rounds,
                       "streams_sweep": args.streams_sweep,
                       "device": device},
            "benchmarks": [
                {"name": name, "us_per_call": round(us, 2), "derived": derived,
                 "calls_per_s": round(1e6 / max(us, 1e-9), 1)}
                for name, us, derived in rows
            ],
        }
        root = pathlib.Path(__file__).resolve().parent.parent
        path = root / "BENCH_router.json"
        path.write_text(json.dumps(out, indent=2) + "\n")
        print(f"wrote {path}")

        # append-only per-PR trajectory: the baseline JSON is overwritten on
        # every refresh, so the history line is what lets a later PR see the
        # headline rows' evolution without archaeology through git
        headline = {
            name: round(us, 2) for name, us, _ in rows
            if name.startswith(("router/", "sweep/ccg@", "sweep/route_step"))
        }
        try:
            commit = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"], cwd=root,
                capture_output=True, text=True, check=True).stdout.strip()
        except (OSError, subprocess.CalledProcessError):
            commit = "unknown"
        snap = {"commit": commit,
                "date": time.strftime("%Y-%m-%dT%H:%M:%S"),
                "device": device,
                "config": out["config"], "headline": headline}
        hist = root / "BENCH_history.jsonl"
        with hist.open("a") as f:
            f.write(json.dumps(snap) + "\n")
        print(f"appended snapshot to {hist}")

    if n_bad:
        sys.exit(f"{n_bad} benchmark(s) regressed >{REGRESSION_FACTOR}x")


if __name__ == "__main__":
    main()
