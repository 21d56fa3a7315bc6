#!/usr/bin/env python3
"""Chip smoke: the routed serving path, once, on a TPU.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # four chips: the sharded router only

One chip runs two phases, through the entry points a user calls:

1. router -- ``ServeSession.run`` (gate-mode r2evid, realization fused, one
   compiled scan) on M=4096 streams, twice on the same chip: with the Pallas
   routing kernels (``force="auto"``) and with the jnp refs (``force="ref"``).
   The compiled scan must hold the kernels, the decisions (route, r, p, v)
   must be identical, and the metrics must agree within ``METRIC_RTOL``.
2. serve -- the launcher's loop (``repro.launch.serve.serve``): 64
   synthesized streams, ``route_many`` then ``session.dispatch`` on the
   continuous-batching executor, over a full-width qwen1.5-0.5b edge pool
   and a qwen3-8b cloud pool at published widths cut to ``CLOUD_LAYERS``
   layers.  Every routed segment must be served once, each tier must serve
   requests, and each pool's bf16 prefill logits must match a float32
   forward of the same weights on a small input.

``--four-chips`` runs only ``ServeSession.run_sharded`` on a 4-device
("data",) mesh at M=4096, gathered and hierarchical, against the dense run
on one device of the same process.

Weights, streams and features come from ``--seed``.  Every phase prints what
it found; a failed check exits non-zero before the last line, which is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without a TPU it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

ROUTER_STREAMS = 4096
ROUTER_ROUNDS = 4
SERVE_STREAMS = 64
SERVE_ROUNDS = 2
SEGMENTS_PER_ROUND = 4
EDGE_ARCH, CLOUD_ARCH = "qwen1.5-0.5b", "qwen3-8b"
# qwen3-8b layers that fit one 16 GB v5e beside the whole edge pool (f32
# weights, bf16 compute).  From compiled.memory_analysis() of both pools'
# prefill (8 x 80 tokens) and slab-decode (16 slots) programs, compiled for
# a described v5e: resident weights + slabs are 11.59 GB at 5 layers and
# the largest program's temporaries add 1.96 GB, a 13.55 GB peak; 6 layers
# would peak at 14.73 GB.
CLOUD_LAYERS = 5
DECODE_TOKENS = 8
# the two router runs differ only in who evaluates the gate / CCG / C6
# arithmetic; decisions must be identical, metrics within this
METRIC_RTOL, METRIC_ATOL = 1e-5, 1e-5
# bf16-compute logits against the float32 forward of the same weights
LOGIT_REL_TOL = 5e-2


class SmokeFailure(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAIL: {msg}")


def require(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def _r2evid_policy(seed: int):
    import jax

    from repro.core.cost_model import SystemConfig
    from repro.core.features import feature_dim
    from repro.core.gating import GateConfig, gate_specs
    from repro.models.params import init_params
    from repro.serving.policy import make_policy

    sys_ = SystemConfig()
    gcfg = GateConfig(d_feature=feature_dim())
    params = init_params(gate_specs(gcfg), jax.random.PRNGKey(seed))
    return sys_, make_policy("r2evid", sys_, gate_cfg=gcfg, gate_params=params)


def _stream(sys_, m: int, rounds: int, seed: int, **sim_kw):
    from repro.serving.simulator import SimConfig, Simulator

    simc = SimConfig(n_tasks=m, n_rounds=rounds, seed=seed,
                     bw_fluctuation=0.2, requirement="fluctuating", **sim_kw)
    return simc, Simulator(sys_, simc).sample_stream(feature_seed=seed)


def _host(mets) -> dict:
    import numpy as np

    return {k: np.asarray(v) for k, v in mets.items()}


def _compare(name, got, want, decisions=("route", "r", "p", "v")):
    """Decisions identical; every other key within the metric tolerance.
    Returns {key: max abs difference}."""
    import numpy as np

    require(set(got) == set(want), f"{name}: keys {sorted(got)} != {sorted(want)}")
    diffs = {}
    for k in sorted(want):
        a, b = got[k], want[k]
        require(a.shape == b.shape, f"{name}: {k} shape {a.shape} != {b.shape}")
        if k in decisions:
            n_bad = int((a != b).sum())
            require(n_bad == 0, f"{name}: {n_bad} of {a.size} {k} decisions differ")
            diffs[k] = 0
            continue
        require(np.isfinite(a).all(), f"{name}: non-finite {k}")
        diffs[k] = float(np.abs(a.astype(np.float64) - b).max())
        require(np.allclose(a, b, rtol=METRIC_RTOL, atol=METRIC_ATOL),
                f"{name}: {k} differs by up to {diffs[k]:.3g}")
    return diffs


def phase_router(m: int, rounds: int, seed: int):
    """``ServeSession.run`` with the Pallas kernels against the jnp refs."""
    import jax

    from repro.serving.session import ServeSession

    sys_, policy = _r2evid_policy(seed)
    _, stream = _stream(sys_, m, rounds, seed)
    mets = {}
    for force in ("auto", "ref"):
        session = ServeSession(policy, m, force=force)
        n_kernels = session.lower_run(stream).as_text().count("tpu_custom_call")
        t0 = time.perf_counter()
        out = session.run(stream)
        jax.block_until_ready(out)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(session.run(stream))
        again_ms = (time.perf_counter() - t0) * 1e3
        print(f"router[{force}] M={m} rounds={rounds}: tpu_custom_call x"
              f"{n_kernels} in the lowered scan; first run (compile + run) "
              f"{first_s:.1f} s, second run {again_ms:.1f} ms", flush=True)
        if force == "auto":
            require(n_kernels > 0, "the compiled session scan holds no Pallas "
                                   "kernel (tpu_custom_call)")
        if force == "ref":
            require(n_kernels == 0, "the ref session scan holds a kernel")
        mets[force] = _host(out)
    diffs = _compare("router pallas vs ref", mets["auto"], mets["ref"])
    routes = mets["ref"]["route"]
    print(f"router parity: route/r/p/v identical on {routes.size} decisions "
          f"(cloud share {routes.mean():.3f}); max |metric diff| "
          + ", ".join(f"{k}={v:.3g}" for k, v in diffs.items()
                      if k not in ("route", "r", "p", "v"))
          + f" (tolerance rtol={METRIC_RTOL}, atol={METRIC_ATOL})", flush=True)


def _check_logits(pool, seed: int):
    """The pool's bf16-compute prefill logits against a float32 forward of
    the same weights, on one 16-token prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import Ctx, prefill

    toks = jax.random.randint(jax.random.PRNGKey(seed), (1, 16), 0,
                              pool.cfg.vocab_size, jnp.int32)
    got, _ = jax.jit(lambda p, b: prefill(pool.ctx, p, b))(
        pool.params, {"tokens": toks})
    ctx32 = Ctx(cfg=dataclasses.replace(pool.cfg, compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):    # not one bf16 MXU pass
        want, _ = jax.jit(lambda p, b: prefill(ctx32, p, b))(
            pool.params, {"tokens": toks})
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    require(got.shape == (1, pool.cfg.vocab_size),
            f"{pool.name}: logits shape {got.shape}")
    require(np.isfinite(got).all(), f"{pool.name}: non-finite logits")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    print(f"pool[{pool.name}] logits vs float32 forward: relative L2 error "
          f"{rel:.2e} (tolerance {LOGIT_REL_TOL}), argmax "
          f"{'agrees' if got.argmax() == want.argmax() else 'differs'}",
          flush=True)
    require(rel < LOGIT_REL_TOL, f"{pool.name}: logits off the float32 "
                                 f"reference by {rel:.3g}")


def phase_serve(edge_cfg, cloud_cfg, streams: int, rounds: int, spr: int,
                seed: int):
    """The launcher's route -> dispatch loop over live tier pools."""
    import jax
    import numpy as np

    from repro.launch.serve import serve
    from repro.serving.pools import make_tier_pools

    t0 = time.perf_counter()
    pools = make_tier_pools(edge_cfg, cloud_cfg)
    for pool in pools.values():
        jax.block_until_ready(pool.params)
        n = sum(x.size for x in jax.tree_util.tree_leaves(pool.params))
        gb = sum(x.nbytes for x in jax.tree_util.tree_leaves(pool.params)) / 1e9
        print(f"pool[{pool.name}]: {pool.cfg.name} {pool.cfg.num_layers} "
              f"layers, d_model {pool.cfg.d_model}, vocab "
              f"{pool.cfg.vocab_size}: {n / 1e9:.3f} B params, {gb:.2f} GB",
              flush=True)
    print(f"pools built in {time.perf_counter() - t0:.1f} s", flush=True)
    for pool in pools.values():
        _check_logits(pool, seed)

    t0 = time.perf_counter()
    out = serve(pools, streams=streams, rounds=rounds, segments_per_round=spr,
                seed=seed, requirement="fluctuating",
                decode_tokens=DECODE_TOKENS)
    print(f"served {rounds} rounds in {time.perf_counter() - t0:.1f} s "
          f"(compiles included)", flush=True)

    served_total = {t: 0 for t in pools}
    for i, rnd in enumerate(out["rounds"]):
        route = np.asarray(rnd["sol"]["route"])
        r = np.asarray(rnd["sol"]["r"])
        for tier in pools:
            lanes = route == tier
            st = rnd["served"].get(tier, {"requests": 0, "tokens": 0})
            want_tok = int((16 * (1 + r[lanes]) + DECODE_TOKENS).sum())
            require(st["requests"] == int(lanes.sum()),
                    f"round {i} tier {tier}: {st['requests']} served of "
                    f"{int(lanes.sum())} routed")
            require(st["tokens"] == want_tok,
                    f"round {i} tier {tier}: {st['tokens']} tokens, "
                    f"expected {want_tok}")
            served_total[tier] += st["requests"]
    executor = out["session"].executor
    for tier, ex in executor.execs.items():
        vocab = pools[tier].cfg.vocab_size
        for comp in ex.completions:
            require(comp.ids.shape == (DECODE_TOKENS,)
                    and (comp.ids >= 0).all() and (comp.ids < vocab).all(),
                    f"tier {tier} stream {comp.stream}: bad ids {comp.ids}")
    for tier, n in served_total.items():
        require(n > 0, f"tier {tier} ({pools[tier].name}) served no request")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print("dispatch check: every routed segment served once; requests per "
          "tier " + ", ".join(f"{pools[t].name}={n}" for t, n in
                              served_total.items())
          + (f"; device peak {peak / 1e9:.2f} GB" if peak else ""), flush=True)


def _shards(x, axis: int = 0) -> int:
    """How many devices hold a distinct slice of x's stream ``axis``."""
    return x.shape[axis] // x.sharding.shard_shape(x.shape)[axis]


def phase_sharded(m: int, rounds: int, seed: int, n_dev: int):
    """``run_sharded`` (gathered, hierarchical) against the dense run."""
    import jax
    import numpy as np

    from repro.serving.session import ServeSession
    from repro.sharding.compat import make_mesh

    sys_, policy = _r2evid_policy(seed)
    # the hierarchical tail partitions the server pools statically
    simc, stream = _stream(sys_, m, rounds, seed, n_edge_servers=2 * n_dev,
                           n_cloud_servers=n_dev)
    mesh = make_mesh((n_dev,), ("data",))
    dense_s = ServeSession(policy, m, sim=simc)
    dense = _host(dense_s.run(stream))
    print(f"dense run on {jax.devices()[0]}: M={m} rounds={rounds}",
          flush=True)
    for hier in (False, True):
        name = "hierarchical" if hier else "gathered"
        session = ServeSession(policy, m, sim=simc, hierarchical=hier)
        t0 = time.perf_counter()
        out = session.run_sharded(mesh, stream)
        jax.block_until_ready(out)
        first_s = time.perf_counter() - t0
        split = _shards(session.state.prev_route)
        require(split == n_dev, f"{name}: stream state held on {split} "
                                f"devices, not split over {n_dev}")
        if hier:
            split = _shards(out["route"], axis=1)
            require(split == n_dev, f"{name}: per-task output on {split} "
                                    f"devices, not split over {n_dev}")
        got = _host(out)
        if hier:
            for k in ("route", "v"):
                n_bad = int((got[k] != dense[k]).sum())
                require(n_bad == 0, f"{name}: {n_bad} {k} decisions differ")
            depth = lambda s: ((sys_.n_res - 1 - s["r"])
                               + (sys_.n_fps - 1 - s["p"]))
            gap = int(np.abs(depth(got) - depth(dense)).max())
            require(gap <= 1, f"{name}: demotion gap {gap} > 1 level")
            detail = (f"route/v identical, max demotion gap {gap} level "
                      f"(bound 1)")
        else:
            diffs = _compare(name, got, dense)
            detail = ("identical decisions, max |metric diff| "
                      + ", ".join(f"{k}={v:.3g}" for k, v in diffs.items()
                                  if k not in ("route", "r", "p", "v")))
        print(f"sharded[{name}] on {n_dev} devices: stream axis split "
              f"{n_dev} ways; run (compile included) {first_s:.1f} s; "
              f"{detail}", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-dense router comparison "
                         "on a 4-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = ROOT / "src"
    require((src / "repro").is_dir(),
            f"no repro package under {src}: run from a checkout of the repo")
    sys.path.insert(0, str(src))
    from repro.runtime.jax_env import device_info, enable_compile_cache

    cache = enable_compile_cache()
    device = device_info()
    require(device["platform"] == "tpu",
            f"no TPU: JAX found platform {device['platform']!r} "
            f"({device['kind']}); this smoke runs only on a TPU")
    print(f"device: {device}; compile cache: {cache}", flush=True)

    if args.four_chips:
        require(device["count"] >= 4,
                f"--four-chips needs 4 devices, found {device['count']}")
        phase_sharded(ROUTER_STREAMS, ROUTER_ROUNDS, args.seed, 4)
    else:
        from repro.configs import get_config
        from repro.launch.serve import tier_configs

        t0 = time.perf_counter()
        phase_router(ROUTER_STREAMS, ROUTER_ROUNDS, args.seed)
        print(f"phase router: {time.perf_counter() - t0:.1f} s", flush=True)
        edge_cfg, cloud_cfg = tier_configs(EDGE_ARCH, CLOUD_ARCH, "full",
                                           CLOUD_LAYERS)
        print(f"cloud cut: {CLOUD_ARCH} at published widths, {CLOUD_LAYERS} "
              f"of {get_config(CLOUD_ARCH).num_layers} layers (fits beside "
              f"the whole "
              f"{EDGE_ARCH} edge pool on one 16 GB chip)", flush=True)
        t0 = time.perf_counter()
        phase_serve(edge_cfg, cloud_cfg, SERVE_STREAMS, SERVE_ROUNDS,
                    SEGMENTS_PER_ROUND, args.seed)
        print(f"phase serve: {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
