#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, in one process on the chip:
for each seed, the numbers the program's run compares (the lower
readings) and the same numbers for the control, the plain reference
computed one precision step below the configuration's and put in the
program's place (the upper readings).

  python3 bench/control.py --workload fleet4096.congested \\
      --seeds 11,12,13 --seconds 5

Prints one JSON line per seed and the largest program reading and smallest
control reading of each number.  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import harness

    bm = harness.benchmark()
    wl = harness.find(bm["workloads"], args.workload, "workload")
    cfg, mix = harness.config(wl["config"]), harness.traffic(wl["traffic"])
    harness.setup_jax(wl["chips"])
    counter = harness.CompileCounter()
    mod = harness.system(cfg["system"])
    lo, hi = {}, {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = mod.Cell(cfg, mix, seed, counter=counter)
        rec = cell.window(args.seconds, harness.spans(False))
        cell.free()
        prog = {k: c["value"] for k, c in cell.check(rec).items()}
        ctl = {k: c["value"] for k, c in mod.control(cell, rec).items()}
        print(json.dumps({"seed": seed, "program": prog, "control": ctl,
                          "failed": rec.failed, "rounds": rec.rounds}),
              flush=True)
        for k, v in prog.items():
            lo[k] = max(lo.get(k, v), v)
        for k, v in ctl.items():
            hi[k] = min(hi.get(k, v), v)
        del cell
    print(json.dumps({"program_max": lo, "control_min": hi}), flush=True)


if __name__ == "__main__":
    main()
