"""Driver for routed serving: each round the router decides every camera's
segment (``ServeSession.route_many``, gate-mode r2evid, Pallas routing
kernels), and the routed segments are served on the live edge and cloud
model pools by the continuous-batching dispatch executor
(``DispatchExecutor``: bucketed ``ModelPool.prefill_batch``,
``insert_slab``, token-level ``decode_slab``).

A segment becomes a request exactly as ``ServeSession.dispatch`` makes it:
``16 * (1 + r)`` prompt tokens ``(i * 131 + j) mod vocab`` for camera i,
``decode_tokens`` greedy tokens.  The harness submits them to the
session's executor and steps it itself, so that the executor's per-call
statistics stay out of the timed loop: ``dispatch`` computes them on the
device with ``jnp.quantile``, one compile per request count.  The cell
counts each pool's decode steps in the window (a pool that is not idle
before a scheduling step decodes in it, as every segment decodes at least
once).

The pools serve the benchmark's weights (``pool_weights``): drawn from
the seed at the published ``initializer_range`` and put in place of each
pool's own draw once it is built, so that every layer moves the served
tokens.  (The program's own draw ties a unit-scale embedding to the edge's
head: each edge step then repeats its input token, whatever its layers
do.)  Every seed serves one fleet (``fleet``): the same segments, in an
order of its own.

Loops: ``open`` -- round k is due ``k * round_period_s`` after the window
opens; a segment's latency runs from its round's due time to its
completion, so a late round carries its lateness.  ``closed`` -- the next
round is routed as soon as the last is served.

The check runs the plain reference (``bench/ref/qwen_ref.py``) over a
sample of finished segments drawn from the seed (the longest prompts among
them), each prompt with its served tokens, and reads the widest gap by
which a served token's logit lies below the reference's best at its
position, per pool, and compares every window round's routed decisions
and gate scores with the router's reference (``systems/router.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import time

import numpy as np

import traffic_gen
from record import Record
from seeds import jax_key, np_rng
from systems.router import gate_weights, system_config


def model_config(name: str, c: dict):
    """The program's ModelConfig for one pool's configuration group."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=name, family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=c["qk_norm"], qkv_bias=c["attention_bias"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        compute_dtype=c["compute_dtype"], param_dtype=c["torch_dtype"])


#: the seed of the one fleet that every run of a pool cell serves
FLEET_SEED = 1


def fleet(mix: dict, cameras: int, d_feature: int, seed: int):
    """A run's round bank and the seed of its gate's weights.

    Every run serves one fleet of the mix, drawn from ``FLEET_SEED`` (each
    camera's rounds and the gate's weights), with its cameras in an order
    drawn from the run's seed: the router then gives every seed the same
    segments in another order, so that a seed changes the order of the
    pools' work and not its amount."""
    bank = traffic_gen.round_bank(mix, cameras, d_feature, FLEET_SEED)
    order = np_rng(seed, "traffic.cameras").permutation(cameras)
    return dataclasses.replace(bank, z=bank.z[:, order], aq=bank.aq[:, order],
                               dx=bank.dx[:, order]), FLEET_SEED


def pool_weights(pool, c: dict, key) -> None:
    """Give a built pool the benchmark's weights (``qwen_ref.make_weights``,
    drawn from ``key`` in one jitted call, each leaf in the type the pool
    keeps it in), in place of the pool's own draw, which is dropped first
    so that one copy is held at a time."""
    import jax

    from ref import qwen_ref

    rc = ref_config(c)
    held = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), pool.params)
    want = jax.tree_util.tree_map(
        lambda w, h: jax.ShapeDtypeStruct(w.shape, h.dtype),
        qwen_ref.weight_shapes(rc), held)
    if want != held:
        raise ValueError(f"the {pool.name} pool's parameter tree is not the "
                         f"reference's: {held} != {want}")
    dtypes = jax.tree_util.tree_map(lambda h: h.dtype, held)
    pool.params = None
    pool.params = qwen_ref.make_weights(rc, key, dtypes)
    jax.block_until_ready(pool.params)


def pool_groups(cfg: dict) -> dict:
    """{tier: (name, configuration group)}: edge = 0, cloud = 1."""
    return {0: ("edge", cfg["edge_model"]), 1: ("cloud", cfg["cloud_model"])}


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, *, counter,
                 pools=None):
        import jax
        import jax.numpy as jnp

        from repro.core.features import feature_dim
        from repro.core.gating import GateConfig
        from repro.core.router import RouterConfig
        from repro.serving.policy import make_policy
        from repro.serving.pools import ModelPool
        from repro.serving.session import ServeSession

        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.m = int(mix["cameras"])
        self.d = feature_dim()
        self.decode_tokens = int(mix["decode_tokens"])
        if self.decode_tokens < 2:
            raise ValueError("decode steps are counted for segments that "
                             "decode at least once: decode_tokens >= 2")
        dep = dict(cfg["deployment"],
                   total_bw_mbps=cfg["uplink_mbps_per_camera"] * self.m)
        self.deployment = dep
        gate = cfg["gate"]
        gcfg = GateConfig(d_feature=self.d, d_hidden=gate["d_hidden"],
                          var_window=gate["var_window"])
        self.bank, self.gate_seed = fleet(mix, self.m, self.d, seed)
        policy = make_policy(
            "r2evid", system_config({"deployment": dep}), gate_cfg=gcfg,
            gate_params=gate_weights(self.gate_seed, self.d, gate["d_hidden"]),
            rcfg=RouterConfig(**cfg["router"]))
        if pools is None:
            pools = {}
            for tier, (name, c) in pool_groups(cfg).items():
                pools[tier] = ModelPool(model_config(name, c),
                                        jax_key(seed, f"pool.{name}"),
                                        name=name)
                pool_weights(pools[tier], c, jax_key(seed, f"weights.{name}"))
        self.session = ServeSession(policy, self.m, force="auto", pools=pools)
        self.vocab = {t: p.cfg.vocab_size for t, p in pools.items()}
        self.k = 0
        self.completions = []          # (round, due, Completion)
        self.decisions = []            # per round served: ((4, M), tau (M,))
        self.window_rounds: list[int] = []
        ex = self.session.executor
        self.decode_steps = dict.fromkeys(ex.execs, 0)
        self.n_res = len(dep["resolutions"])
        # every shape this traffic can use: each prompt length at each
        # prefill batch pad, each slot count a prefill can fill, one decode
        for tier, pex in ex.execs.items():
            pool = pex.pool
            b = 1
            while True:
                for r in range(self.n_res):
                    ids, cache = pool.prefill_batch(
                        jnp.zeros((b, 16 * (1 + r)), jnp.int32))
                    for n in range(1, b + 1):
                        if n == b or (n > b // 2):
                            pex.slab = pool.insert_slab(pex.slab, cache,
                                                        list(range(n)))
                if b >= pex.max_prefill_batch:
                    break
                b = min(2 * b, pex.max_prefill_batch)
            ids, pex.slab = pool.decode_slab(pex.slab, pex.last_ids)
            jax.block_until_ready(ids)
        # then the cell's own traffic until a round compiles nothing
        for _ in range(int(mix.get("max_warmup_rounds", 4))):
            before = counter.total()
            self._route_and_serve(lambda name: contextlib.nullcontext(), None)
            if counter.total() == before:
                break
        ex.reset_measurements()
        self.completions.clear()

    # -- one round ----------------------------------------------------------
    def _requests(self, route, r):
        from repro.serving.dispatch import Request

        reqs = []
        for i in range(self.m):
            tier = int(route[i])
            n_tok = 16 * (1 + int(r[i]))
            toks = (i * 131 + np.arange(n_tok)) % self.vocab[tier]
            reqs.append(Request(stream=i, tier=tier,
                                tokens=toks.astype(np.int32),
                                decode_tokens=self.decode_tokens,
                                enqueue_t=0.0))
        return reqs

    def _route_and_serve(self, span, due, route_s=None):
        import jax

        b = self.k % self.bank.rounds
        ex = self.session.executor
        t0 = time.perf_counter()
        with span("bench.route"):
            sol = self.session.route_many(self.bank.dx[b][None],
                                          self.bank.z[b], self.bank.aq[b])
            route, r, p, v, tau = jax.device_get(
                tuple(sol[k][0] for k in ("route", "r", "p", "v", "tau")))
        self.decisions.append((np.stack([route, r, p, v]), tau))
        if route_s is not None:
            route_s.append(time.perf_counter() - t0)
        marks = {t: len(e.completions) for t, e in ex.execs.items()}
        with span("bench.dispatch"):
            ex.submit(self._requests(route, r))
            while not ex.idle:
                for t, e in ex.execs.items():
                    self.decode_steps[t] += not e.idle
                with span("bench.step"):
                    ex.step()
        for t, e in ex.execs.items():
            self.completions += [(self.k, due, c)
                                 for c in e.completions[marks[t]:]]
        self.k += 1

    # -- the measured window --------------------------------------------------
    def window(self, seconds: float, span) -> Record:
        open_loop = self.mix["loop"] == "open"
        drain_s = float(self.mix.get("drain_s", 60.0))
        self.completions.clear()
        self.decode_steps = dict.fromkeys(self.decode_steps, 0)
        route_s = []
        t0 = time.perf_counter()
        rounds = 0
        while True:
            now = time.perf_counter()
            if open_loop:
                due = t0 + traffic_gen.due_time(self.mix, rounds)
                if due - t0 >= seconds:
                    break
                if now - t0 >= seconds + drain_s:
                    rounds += 1          # due, never served: failed
                    continue
                if due > now:
                    with span("bench.wait_due"):
                        time.sleep(due - now)
            else:
                if now - t0 >= seconds:
                    break
                due = now
            self.window_rounds.append(self.k)
            with span("bench.round"):
                self._route_and_serve(span, due, route_s)
            rounds += 1
        end = time.perf_counter()
        window_s = seconds if open_loop else end - t0
        segs = [{"round": k, "due": due, "enqueue": c.enqueue_t,
                 "admit": c.admit_t, "finish": c.finish_t, "tier": c.tier,
                 "tokens": c.tokens, "prompt": c.n_prefill,
                 "decoded": len(c.ids)}
                for k, due, c in self.completions]
        attempted = rounds * self.m
        report_service(segs, self.decode_steps)
        return Record(
            window_s=window_s, attempted=attempted,
            failed=attempted - len(segs), rounds=rounds, segments=segs,
            route_s=route_s,
            tokens_in_window=sum(s["tokens"] for s in segs
                                 if s["finish"] <= t0 + window_s),
            extra={"cameras": self.m, "t0": t0, "end": end,
                   "decode_steps": dict(self.decode_steps),
                   "configs": {t: c for t, (_, c) in
                               pool_groups(self.cfg).items()}})

    def free(self):
        """Drop the program's state (pools, slabs, session) before the
        reference runs."""
        self.kept = [(k, c.tier, c.stream, c.n_prefill, np.asarray(c.ids))
                     for k, _, c in self.completions]
        self.session = None
        self.completions = []
        gc.collect()

    # -- comparison with the plain reference ----------------------------------
    def sample(self, tier: int) -> list:
        """Finished segments of one pool to check: ``check_segments`` drawn
        from the seed, half of them among the longest prompts."""
        rows = [x for x in self.kept if x[1] == tier]
        want = int(self.mix["check_segments"])
        if len(rows) <= want:
            return rows
        rng = np_rng(self.seed, f"check.segments.{tier}")
        longest = max(x[3] for x in rows)
        long_rows = [i for i, x in enumerate(rows) if x[3] == longest]
        pick = list(rng.choice(long_rows, min(want // 2, len(long_rows)),
                               replace=False))
        rest = [i for i in range(len(rows)) if i not in set(pick)]
        pick += list(rng.choice(rest, want - len(pick), replace=False))
        return [rows[i] for i in sorted(pick)]

    def router_config(self) -> dict:
        return {"gate": self.cfg["gate"], "router": self.cfg["router"],
                "deployment": self.deployment}

    def check(self, rec: Record) -> dict:
        from systems.router import compare, reference

        lim = self.cfg["limits"]
        picked = self.window_rounds
        if not picked:
            return {"rounds_unchecked": {"value": 1, "limit": 0}}
        got_dec = np.stack([self.decisions[k][0] for k in picked])
        got_tau = np.stack([self.decisions[k][1] for k in picked])
        want_dec, want_tau, ties = reference(
            self.router_config(), self.bank, self.gate_seed, self.k, picked,
            got_dec[:, 0])
        out = compare(got_dec, got_tau, want_dec, want_tau, ties, lim)
        for tier, (name, c) in pool_groups(self.cfg).items():
            rows = self.sample(tier)
            if not rows:
                out[f"{name}_unchecked"] = {"value": 1, "limit": 0}
                continue
            gap = logit_gap(c, self.seed, name, rows)
            out[f"{name}_logit_gap"] = {
                "value": gap, "limit": self.cfg["limits"][f"{name}_logit_gap"]}
        return out


def report_service(segs, decode_steps):
    """Each round's service time, from its due time to its last completion,
    on standard error: where a wide spread of the end-to-end metrics comes
    from (stalls in a few rounds, or every round slower)."""
    import statistics

    last = {}
    for s in segs:
        last[s["round"]] = max(last.get(s["round"], 0.0), s["finish"] - s["due"])
    if not last:
        return
    svc = sorted(last.values())
    print(f"bench: {len(svc)} rounds served: service median "
          f"{statistics.median(svc) * 1e3:.4f} ms, max {svc[-1] * 1e3:.4f} ms "
          f"(rounds in order: "
          f"{' '.join(f'{last[k] * 1e3:.1f}' for k in sorted(last))}); "
          f"decode steps per pool {decode_steps}", file=sys.stderr, flush=True)


def ref_config(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "num_hidden_layers",
            "vocab_size", "rms_norm_eps", "rope_theta", "tie_word_embeddings",
            "attention_bias", "qk_norm", "initializer_range")
    return tuple((k, c[k]) for k in keys)


def reference_logits(c: dict, seed: int, name: str, rows, fp8=False):
    """Reference logits at every served token's position, per row:
    [(row, (decoded, V) logits)], weights made anew from the seed."""
    import jax
    import jax.numpy as jnp

    from ref import qwen_ref

    rc = ref_config(c)
    params = qwen_ref.make_weights(rc, jax_key(seed, f"weights.{name}"))
    out = []
    batch = 8
    for i in range(0, len(rows), batch):
        part = rows[i:i + batch]
        seqs = []
        for k, tier, stream, n_prompt, ids in part:
            prompt = (stream * 131 + np.arange(n_prompt)) % c["vocab_size"]
            seqs.append(np.concatenate([prompt, ids[:-1]]).astype(np.int32))
        width = max(len(s) for s in seqs)
        toks = np.zeros((len(seqs), width), np.int32)
        pick = np.zeros((len(seqs), len(part[0][4])), np.int32)
        for j, (s, row) in enumerate(zip(seqs, part)):
            toks[j, :len(s)] = s
            pick[j] = row[3] - 1 + np.arange(len(row[4]))
        lg = qwen_ref.logits(rc, params, jnp.asarray(toks), jnp.asarray(pick),
                             fp8=fp8)
        lg = np.asarray(jax.device_get(lg))
        out += [(row, lg[j]) for j, row in enumerate(part)]
    del params
    return out


def control(cell, rec: Record) -> dict:
    """The control's compared numbers: at every served position of the
    checked segments, the gap below the reference's best of the token that
    the reference computed with e4m3 operands (the step below the
    configured bfloat16) puts first; and the router's control (the gate's
    products at ``Precision.HIGH``) on the window's rounds."""
    from ref import router_ref
    from systems.router import compare, reference

    lim = cell.cfg["limits"]
    args = (cell.router_config(), cell.bank, cell.gate_seed, cell.k,
            cell.window_rounds)
    want_dec, want_tau, ties = reference(*args)
    got_dec, got_tau, _ = reference(*args, mm=router_ref.mm_high)
    out = compare(got_dec, got_tau, want_dec, want_tau, ties, lim)
    for tier, (name, c) in pool_groups(cell.cfg).items():
        rows = cell.sample(tier)
        ref = reference_logits(c, cell.seed, name, rows)
        low = reference_logits(c, cell.seed, name, rows, fp8=True)
        gap = 0.0
        for (row, lg), (_, lq) in zip(ref, low):
            first = lq.argmax(axis=-1)
            gap = max(gap, float((lg.max(axis=-1)
                                  - lg[np.arange(len(first)), first]).max()))
        out[f"{name}_logit_gap"] = {"value": gap,
                                    "limit": lim[f"{name}_logit_gap"]}
    return out


def logit_gap(c: dict, seed: int, name: str, rows) -> float:
    """Widest gap, over every served token of ``rows``, between the
    reference's best logit at its position and the served token's."""
    gap = 0.0
    for row, lg in reference_logits(c, seed, name, rows):
        ids = row[4]
        best = lg.max(axis=-1)
        got = lg[np.arange(len(ids)), ids]
        gap = max(gap, float((best - got).max()))
    return gap
