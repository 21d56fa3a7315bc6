"""Serving launcher: R2E-VID routed inference over live edge/cloud pools.

  PYTHONPATH=src python -m repro.launch.serve --rounds 4 --streams 8
  PYTHONPATH=src python -m repro.launch.serve --preset full --cloud-layers 6

Video streams are synthesized, motion features drive the temporal gate, and
one :class:`~repro.serving.session.ServeSession` owns the whole serving
stack: the gate-mode ``r2evid`` policy (RouterState carry threaded through
the compiled, donated decide scan), the config bundle, and the live tier
pools the routed token workloads dispatch onto (``session.dispatch``).

Each round consumes ``--segments-per-round`` segments per stream in ONE
compiled ``lax.scan`` (``session.route_many``): the gate recurrence carries
across segments and rounds (no window re-scan, no per-segment Python
dispatch, carry buffers donated — never copied), and the last segment's
solution drives the round's dispatch.  ``--policy`` swaps in any registered
policy (baselines route the same loop; they simply ignore the features).
``--gate-resync`` sets the cadence at which the batched gate recomputes its
running volatility sums from the exact ring buffer (0 = once per window;
1 = every step, drift-free).

``--preset smoke`` (the default) builds the tier pools from the 2-layer
smoke variants; ``--preset full`` builds them at their published widths,
with the cloud model cut to ``--cloud-layers`` layers.  :func:`serve` is the
one loop behind both this entry point and ``chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.cost_model import SystemConfig
from repro.core.features import feature_dim, segment_features
from repro.core.gating import GateConfig, gate_specs
from repro.data.video import VideoConfig, generate_stream, make_task_batch
from repro.models.params import init_params
from repro.runtime.jax_env import enable_compile_cache
from repro.serving.policy import make_policy
from repro.serving.pools import make_tier_pools
from repro.serving.session import ServeSession


def tier_configs(edge_arch: str, cloud_arch: str, preset: str = "smoke",
                 cloud_layers: int | None = None):
    """(edge, cloud) model configs: the smoke variants, or the published
    widths with the cloud depth cut to ``cloud_layers`` (None = whole)."""
    if preset == "smoke":
        return get_smoke_config(edge_arch), get_smoke_config(cloud_arch)
    if preset != "full":
        raise ValueError(f"preset must be 'smoke' or 'full', got {preset!r}")
    edge, cloud = get_config(edge_arch), get_config(cloud_arch)
    if cloud_layers is not None:
        if not 0 < cloud_layers <= cloud.num_layers:
            raise ValueError(f"cloud_layers must be in 1..{cloud.num_layers}, "
                             f"got {cloud_layers}")
        cloud = dataclasses.replace(cloud, num_layers=cloud_layers)
    return edge, cloud


def serve(pools, *, streams: int, rounds: int, segments_per_round: int,
          policy: str = "r2evid", gate_resync: int = 0, seed: int = 0,
          requirement: str = "stable", decode_tokens: int = 8) -> dict:
    """Route ``rounds`` rounds of synthesized streams and dispatch every
    round's routed segments onto ``pools`` (tier -> ModelPool).
    ``requirement`` draws the per-stream accuracy floors (paper §4.1.2:
    "stable" U[0.6, 0.7] or "fluctuating" U[0.5, 0.8]).

    Returns ``{"rounds": [per-round dict], "pools": {name: stats summary},
    "feedback": executor feedback, "session": the ServeSession}``; each
    round dict holds the last segment's solution, the per-tier dispatch
    stats, and the route / serve wall-clock seconds.
    """
    sys_ = SystemConfig()
    if policy == "r2evid":
        gcfg = GateConfig(d_feature=feature_dim(), resync_period=gate_resync)
        gparams = init_params(gate_specs(gcfg), jax.random.PRNGKey(seed))
        pol = make_policy("r2evid", sys_, gate_cfg=gcfg, gate_params=gparams)
    else:
        pol = make_policy(policy, sys_)
    session = ServeSession(pol, n_streams=streams, pools=pools)

    spr = segments_per_round
    vcfg = VideoConfig()
    clips = [generate_stream(vcfg, n_segments=rounds * spr,
                             rng=np.random.default_rng(seed * 100003 + i))
             for i in range(streams)]
    aq = jnp.asarray(make_task_batch(streams, requirement, seed=seed))
    # (streams, total_segments, d) segment features, computed once per stream
    dx_all = jnp.stack([
        segment_features(jnp.asarray(fr), vcfg.frames_per_segment)
        for fr, _ in clips
    ])

    out_rounds = []
    for rnd in range(rounds):
        z = jnp.asarray([m[rnd * spr:(rnd + 1) * spr].mean() for _, m in clips],
                        jnp.float32)
        t_route = time.perf_counter()
        # stream this round's segments through the session in one lax.scan
        dx_seq = jnp.swapaxes(dx_all[:, rnd * spr:(rnd + 1) * spr], 0, 1)
        sols = session.route_many(dx_seq, z, aq)
        sol = jax.tree_util.tree_map(lambda x: x[-1], sols)
        jax.block_until_ready(sol["route"])
        route_s = time.perf_counter() - t_route

        t0 = time.perf_counter()
        served = session.dispatch(sol, decode_tokens=decode_tokens)
        serve_s = time.perf_counter() - t0
        route = np.asarray(sol["route"])
        taus = sol.get("tau")
        print(f"round {rnd}: edge={int((route == 0).sum())} "
            f"cloud={int((route == 1).sum())} "
            + (f"mean_tau={float(np.asarray(taus).mean()):.3f} "
               if taus is not None else "")
            + f"route={route_s * 1e3:.0f}ms serve={serve_s * 1e3:.0f}ms")
        for tier, st in sorted(served.items()):
            print(f"  tier{tier}: {st['requests']} req {st['tokens']} tok "
                f"{st['tokens_per_s']:.0f} tok/s "
                f"p50={st['p50_s'] * 1e3:.0f}ms p99={st['p99_s'] * 1e3:.0f}ms")
        out_rounds.append({"sol": sol, "served": served,
                           "route_s": route_s, "serve_s": serve_s})

    fb = session.feedback()
    print(f"feedback: bw_mult={np.round(np.asarray(fb['bw_mult']), 3).tolist()}"
        f" (apply_feedback folds this into the next round's observation)")
    summaries = {}
    for tier, pool in session.pools.items():
        s = pool.stats.summary()
        summaries[pool.name] = s
        print(f"pool[{pool.name}]: requests={s['requests']} "
            f"tokens={s['tokens']} busy={s['busy_s']:.2f}s "
            f"throughput={s['tokens_per_s']:.0f} tok/s "
            f"p50={s['p50_s'] * 1e3:.0f}ms p99={s['p99_s'] * 1e3:.0f}ms")
    return {"rounds": out_rounds, "pools": summaries, "feedback": fb,
            "session": session}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--segments-per-round", type=int, default=8)
    ap.add_argument("--edge-arch", default="qwen1.5-0.5b")
    ap.add_argument("--cloud-arch", default="qwen3-8b")
    ap.add_argument("--preset", default="smoke", choices=("smoke", "full"),
                    help="tier-pool widths: 2-layer smoke variants, or the "
                         "published widths")
    ap.add_argument("--cloud-layers", type=int, default=None,
                    help="with --preset full: cut the cloud model to this "
                         "many layers (default: whole)")
    ap.add_argument("--policy", default="r2evid",
                    help="registered policy name (r2evid, a2_cloud_only, "
                         "jcab, rdap, sniper)")
    ap.add_argument("--gate-resync", type=int, default=0,
                    help="volatility resync cadence in steps (0 = per window)")
    args = ap.parse_args()
    enable_compile_cache()

    edge_cfg, cloud_cfg = tier_configs(args.edge_arch, args.cloud_arch,
                                       args.preset, args.cloud_layers)
    serve(make_tier_pools(edge_cfg, cloud_cfg), streams=args.streams,
          rounds=args.rounds, segments_per_round=args.segments_per_round,
          policy=args.policy, gate_resync=args.gate_resync)


if __name__ == "__main__":
    main()
