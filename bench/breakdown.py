#!/usr/bin/env python3
"""Where a router cell's round goes, read from the program's own spans and
scopes, and what those spans cost.

  python3 bench/breakdown.py --workload fleet4096.congested \\
      --seeds 11 12 13 --seconds 10 [--out DIR]

For each seed, in one process, the cell is built and warmed up as a run of
``bench/run.py`` builds it, then measured in eight windows:

- four unprofiled windows of ``--seconds``, the program's spans off, on, on
  and off (``repro.runtime.spans``): ``round_ms_p95`` of each, which is what
  the spans cost a deployment that turns them on without a profiler;
- four profiled windows of the mix's ``trace_seconds``, the program's spans
  off (as in a ``--trace 1`` run of ``bench/run.py``), on, on and off: what
  the spans cost inside a profiled window.

The last profiled window with the spans on is reduced by
``program_trace.ProgramTrace`` (the traces and the compiled decide
program's HLO text are kept under ``--out/<seed>/`` when it is given).
Standard error gets one line of ``program_trace.report`` per seed;
standard output one JSON object per seed with the windows'
``round_ms_p95``, the readings of ``program_trace.metrics``, the split of
the launch and the longest idle gaps, labelled by the innermost span.
Needs a TPU, as ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402
import program_trace  # noqa: E402

P95 = harness.reader("round_ms_p95")


def profiled(cell, seconds: float, out: pathlib.Path, program_spans: bool):
    """One profiled window (the harness's spans on, the program's as
    asked); returns (record, path of the trace)."""
    import jax

    from repro.runtime import spans

    out.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(out))
    spans.enable(program_spans)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            rec = cell.window(seconds, harness.spans(True))
    finally:
        spans.enable(False)
        jax.profiler.stop_trace()
    return rec, sorted(out.glob("**/*.xplane.pb"))[-1]


def one_seed(cfg: dict, mix: dict, seed: int, seconds: float,
             out: pathlib.Path, **planes) -> dict:
    """The eight windows of one seed of a router cell (configuration, mix);
    ``planes`` point the trace reduction at other planes than a TPU's."""
    from repro.runtime import spans
    from repro.serving.session import _decide_step

    cell = harness.system(cfg["system"]).Cell(
        cfg, mix, seed, counter=harness.CompileCounter())
    sess = cell.session
    hlo = _decide_step.lower(sess.policy, sess.state, cell._obs(0)) \
        .compile().as_text()
    out.mkdir(parents=True, exist_ok=True)
    (out / "decide.hlo.txt").write_text(hlo)

    p95 = {"off": [], "on": []}
    for on in (False, True, True, False):
        spans.enable(on)
        try:
            rec = cell.window(seconds, harness.spans(False))
        finally:
            spans.enable(False)
        p95["on" if on else "off"].append(P95(rec))
    traced = float(mix.get("trace_seconds", seconds))
    p95.update(traced_off=[], traced_on=[])
    kept = []
    for on in (False, True, True, False):
        tag = "on" if on else "off"
        rec, path = profiled(cell, traced, out / f"spans_{tag}", on)
        p95[f"traced_{tag}"].append(P95(rec))
        kept += [path] if on else []

    cell.free()

    t = program_trace.ProgramTrace(str(kept[-1]), hlo_text=hlo, **planes)
    print(f"bench: seed {seed}: {program_trace.report(t)}", file=sys.stderr,
          flush=True)
    n = t.span_count("bench.round")
    idle = {s: t.idle_under_s(s) for s in ("r2e.launch", "bench.fetch",
                                            "bench.round")}
    idle["window"] = t.idle_share * t.window_s
    return {"seed": seed, "round_ms_p95": p95,
            "metrics": program_trace.metrics(t),
            "rounds_traced": n, "busy_s": t.busy_s, "window_s": t.window_s,
            "scope_us_per_round": {
                s or "any": None if (v := t.scope_time_s(s)) is None
                else v * 1e6 / n for s in program_trace.SCOPES + (None,)},
            "launch_us_per_round": {k: v * 1e6 / n for k, v in
                                    (t.launch_split() or {}).items()},
            "idle_us_per_round": {s: None if v is None else v * 1e6 / n
                                  for s, v in idle.items()},
            "idle_gaps": t.idle_gaps(10),
            "host_us_per_round_in_launch": [
                [line, ev, v * 1e6 / n]
                for line, ev, v in t.host_events_in("r2e.launch", 15)]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", help="keep each seed's traces and HLO text "
                    "here (about 30 MB a trace); by default they go to a "
                    "temporary directory, removed at the end")
    args = ap.parse_args(argv)

    wl = harness.find(harness.benchmark()["workloads"], args.workload,
                      "workload")
    cfg, mix = harness.config(wl["config"]), harness.traffic(wl["traffic"])
    if cfg["system"] != "router":
        raise SystemExit(f"bench: {args.workload} runs no decide program")
    device = harness.setup_jax(wl["chips"])
    with tempfile.TemporaryDirectory(prefix="breakdown_") as tmp:
        for seed in args.seeds:
            got = one_seed(cfg, mix, seed, args.seconds,
                           pathlib.Path(args.out or tmp) / str(seed))
            got.update(workload=args.workload, device=device)
            print(json.dumps(got), flush=True)


if __name__ == "__main__":
    main()
