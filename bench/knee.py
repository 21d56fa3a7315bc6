#!/usr/bin/env python3
"""Find the knee of an open-loop serving mix on the chip: the largest camera
count at which every round is served before the next one is due.

  python3 bench/knee.py --workload edgecloud-qwen.live --cameras 64,96,128 \\
      --rounds 8 --seed 5

For each camera count it serves ``--rounds`` rounds of the mix at its own
period (the pools built once) and prints each round's service time, from
its due time to its last completion.  The cell then runs at 4/5 of the knee.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--cameras", required=True)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import harness

    bm = harness.benchmark()
    wl = harness.find(bm["workloads"], args.workload, "workload")
    cfg, mix = harness.config(wl["config"]), harness.traffic(wl["traffic"])
    harness.setup_jax(wl["chips"])
    counter = harness.CompileCounter()
    mod = harness.system(cfg["system"])
    pools = None
    period = float(mix["round_period_s"])
    for m in [int(x) for x in args.cameras.split(",")]:
        cell = mod.Cell(cfg, dict(mix, cameras=m), args.seed, counter=counter,
                        pools=pools)
        pools = cell.session.pools
        rec = cell.window(args.rounds * period, harness.spans(False))
        per_round = {}
        for s in rec.segments:
            per_round[s["round"]] = max(per_round.get(s["round"], 0.0),
                                        s["finish"] - s["due"])
        service = [per_round[k] for k in sorted(per_round)]
        print(json.dumps({"cameras": m, "service_s": service,
                          "max_s": max(service), "keeps_up":
                          max(service) < period, "failed": rec.failed}),
              flush=True)
        cell.session = None


if __name__ == "__main__":
    main()
