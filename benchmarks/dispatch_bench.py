"""Dispatch executor benchmark: continuous batching vs the serial oracle.

  PYTHONPATH=src python benchmarks/dispatch_bench.py [--streams 16] [--waves 3]
  PYTHONPATH=src python benchmarks/dispatch_bench.py --json   # + BENCH_dispatch.json
  PYTHONPATH=src python benchmarks/dispatch_bench.py --check  # speedup gate

Prints ``name,us_per_call,derived`` CSV lines (the repo benchmark contract):

  dispatch/serial@{mix}     — per-request latency of the serial oracle
                              (grouped ``serve_segment`` calls, no queueing,
                              no cross-batch decode merge) on a mixed-
                              fidelity staggered-arrival workload, with the
                              derived end-to-end tokens/s
  dispatch/continuous@{mix} — the same workload through the continuous-
                              batching executor (bucketed prefills + token-
                              level slab decode, waves submitted mid-flight),
                              derived tokens/s and the speedup over serial
  dispatch/tier{t}@{mix}    — the executor's measured per-tier tail: p50
                              request sojourn as the latency column, p99 and
                              tier tokens/s in the derived field

Mixes are edge/cloud arrival splits (the routed tier of each request):
``balanced`` (50/50), ``edge_heavy`` (75/25), ``cloud_heavy`` (25/75).

With ``--json`` the rows are written to ``BENCH_dispatch.json`` and a
one-line snapshot appended to ``BENCH_history.jsonl``.  With ``--check``
the run becomes the CI gate: continuous batching must not be slower than
the serial oracle (tokens/s ratio >= ``MIN_SPEEDUP``) at any mix.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import time

import numpy as np

from repro.runtime.jax_env import device_info, enable_compile_cache

# --check fails any mix whose continuous/serial tokens-per-second ratio is
# below this (1.0 = "not slower"; headroom left for noisy shared runners is
# intentionally NOT granted — continuous batching that loses to a serial
# loop is a scheduling bug, not noise)
MIN_SPEEDUP = 1.0

MIXES = {"balanced": 0.5, "edge_heavy": 0.75, "cloud_heavy": 0.25}


def make_wave(pools, wave: int, n: int, edge_frac: float, seed: int,
              decode_tokens: int):
    """One arrival wave: mixed fidelity (r in {0,1,2} -> 16/32/48-token
    prompts), tiers split by ``edge_frac``."""
    from repro.serving.dispatch import Request

    rng = np.random.default_rng(seed * 1000 + wave)
    reqs = []
    for i in range(n):
        stream = wave * n + i
        tier = 0 if rng.uniform() < edge_frac else 1
        n_tok = 16 * (1 + int(rng.integers(0, 3)))
        vocab = pools[tier].cfg.vocab_size
        toks = ((stream * 131 + np.arange(n_tok)) % vocab).astype(np.int32)
        reqs.append(Request(stream=stream, tier=tier, tokens=toks,
                            decode_tokens=decode_tokens))
    return reqs


def run_serial(pools, waves):
    """The serial baseline: each wave's requests served back-to-back through
    grouped ``serve_segment`` calls (a wave cannot overlap the previous one
    — the serial path has no queue to hold arrivals)."""
    from repro.serving.dispatch import serve_serial_oracle
    import dataclasses

    t0 = time.perf_counter()
    for wave in waves:
        serve_serial_oracle(pools, [dataclasses.replace(r) for r in wave])
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) + r.decode_tokens for w in waves for r in w)
    return dt, toks


def run_continuous(ex, waves, stagger_steps: int):
    """Waves submitted mid-flight: each wave lands after ``stagger_steps``
    scheduling iterations of the previous one — the staggered-arrival
    pattern the executor's admit/decode interleave is built for."""
    import dataclasses

    t0 = time.perf_counter()
    for wave in waves:
        ex.submit([dataclasses.replace(r) for r in wave])
        for _ in range(stagger_steps):
            ex.step()
    ex.drain()
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) + r.decode_tokens for w in waves for r in w)
    return dt, toks


def bench_dispatch(streams: int, waves: int, decode_tokens: int,
                   stagger_steps: int, n_slots: int):
    from repro.configs import get_smoke_config
    from repro.serving.dispatch import DispatchExecutor
    from repro.serving.pools import make_tier_pools

    pools = make_tier_pools(get_smoke_config("qwen1.5-0.5b"),
                            get_smoke_config("qwen3-8b"))
    ex = DispatchExecutor(pools, n_slots=n_slots)

    rows, speedups = [], {}
    for mix, edge_frac in MIXES.items():
        wv = [make_wave(pools, w, streams, edge_frac, seed=7,
                        decode_tokens=decode_tokens)
              for w in range(waves)]
        n_req = streams * waves
        # untimed pass compiles every (bucket, length) prefill shape and the
        # slab decode for BOTH paths, so the timed pass measures scheduling
        run_serial(pools, wv)
        run_continuous(ex, wv, stagger_steps)

        ser_dt, toks = run_serial(pools, wv)
        ex.reset_measurements()
        mark = {t: len(e.completions) for t, e in ex.execs.items()}
        con_dt, _ = run_continuous(ex, wv, stagger_steps)

        ser_tps, con_tps = toks / ser_dt, toks / con_dt
        speedup = con_tps / ser_tps
        speedups[mix] = speedup
        rows.append((f"dispatch/serial@{mix}", ser_dt / n_req * 1e6,
                     f"tokens_per_s={ser_tps:.0f}"))
        rows.append((f"dispatch/continuous@{mix}", con_dt / n_req * 1e6,
                     f"tokens_per_s={con_tps:.0f};speedup={speedup:.2f}x"))
        for t in sorted(ex.execs):
            st = ex._tier_stats(t, since=mark[t])
            if st["requests"] == 0:
                continue
            rows.append((
                f"dispatch/tier{t}@{mix}", st["p50_s"] * 1e6,
                f"p99_us={st['p99_s'] * 1e6:.0f};"
                f"tokens_per_s={st['tokens_per_s']:.0f}"))
    return rows, speedups


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=16,
                    help="requests per arrival wave")
    ap.add_argument("--waves", type=int, default=3)
    ap.add_argument("--decode-tokens", type=int, default=16,
                    help="decode depth per request (token-level batching "
                         "wins grow with decode share)")
    ap.add_argument("--stagger-steps", type=int, default=4,
                    help="scheduling steps between wave arrivals")
    ap.add_argument("--n-slots", type=int, default=8,
                    help="cache-slot slab size per tier (right-size to the "
                         "per-tier arrival rate: idle slots are overcompute)")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_dispatch.json next to the repo root")
    ap.add_argument("--check", action="store_true",
                    help="fail unless continuous tokens/s >= %.2fx serial "
                         "at every mix" % MIN_SPEEDUP)
    args = ap.parse_args()
    enable_compile_cache()
    device = device_info()
    print(f"device: {device}")

    rows, speedups = bench_dispatch(args.streams, args.waves,
                                    args.decode_tokens, args.stagger_steps,
                                    args.n_slots)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    n_bad = 0
    if args.check:
        for mix, s in speedups.items():
            if s < MIN_SPEEDUP:
                print(f"CHECK FAIL: {mix} continuous/serial speedup "
                      f"{s:.2f}x < {MIN_SPEEDUP:.2f}x")
                n_bad += 1
        if not n_bad:
            print(f"check ok: min speedup "
                  f"{min(speedups.values()):.2f}x >= {MIN_SPEEDUP:.2f}x")

    if args.json:
        out = {
            "config": {"streams": args.streams, "waves": args.waves,
                       "decode_tokens": args.decode_tokens,
                       "stagger_steps": args.stagger_steps,
                       "n_slots": args.n_slots,
                       "device": device},
            "benchmarks": [
                {"name": name, "us_per_call": round(us, 2), "derived": derived}
                for name, us, derived in rows
            ],
            "speedups": {m: round(s, 3) for m, s in speedups.items()},
        }
        root = pathlib.Path(__file__).resolve().parent.parent
        path = root / "BENCH_dispatch.json"
        path.write_text(json.dumps(out, indent=2) + "\n")
        print(f"wrote {path}")

        headline = {f"dispatch/speedup@{m}": round(s, 3)
                    for m, s in speedups.items()}
        try:
            commit = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"], cwd=root,
                capture_output=True, text=True, check=True).stdout.strip()
        except (OSError, subprocess.CalledProcessError):
            commit = "unknown"
        hist = root / "BENCH_history.jsonl"
        line = {"commit": commit, "bench": "dispatch",
                "date": time.strftime("%Y-%m-%d"),
                "device": device, "headline": headline}
        with hist.open("a") as f:
            f.write(json.dumps(line) + "\n")
        print(f"appended {hist}")

    raise SystemExit(1 if n_bad else 0)


if __name__ == "__main__":
    main()
