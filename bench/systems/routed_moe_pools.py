"""The system of routed serving with an expert model in the cloud pool: the
router and the dispatch path of ``routed_pools`` (``ServeSession.route_many``,
``DispatchExecutor``, ``ModelPool.prefill_batch`` / ``insert_slab`` /
``decode_slab``), with the edge pool a dense decoder (``edge_model``, as in
``routed_pools``) and the cloud pool Moonlight-16B-A3B, whose published
configuration the file holds at its top level (``cloud_group``): latent
attention over a latent slab cache, a dense first layer, then
expert layers that route over every expert and compute the experts this
chip holds, dropless, plus the shared experts.

Each pool decodes over a slab of its own size (``serving.slots``).  The
pools serve the benchmark's weights: the edge's from ``qwen_ref``, the
cloud's from ``moonlight_ref``, each drawn from the seed and put in place
of the pool's own draw.

What the window adds to ``routed_pools``'s record (``Record.extra``):

- ``expert_counters``: the cloud slab's expert counters over the window's
  decode steps (``expert_rows``, the rows its held experts computed, and
  ``experts_hit``, the held experts that got any; summed over steps and
  layers on the device, read once before and once after the window);
- in a traced window, host spans around the cloud pool's calls
  (``bench.cloud.decode``, ``bench.cloud.prefill``, ``bench.cloud.insert``;
  decode and prefill return only once the device is done, so their device
  work lies inside the span), and ``cloud_decode_hlo``: the compiled cloud
  decode program's HLO text, whose ``op_name`` paths carry the program's
  named scopes (``moe.experts`` ...), read after the window.

The check compares every window round's decisions with the router's
reference, and per pool the widest gap by which a served token's logit
lies below its reference's best (``qwen_ref`` for the edge,
``moonlight_ref`` for the cloud), on a sample of finished segments drawn
from the seed, each prompt with its served tokens in one forward pass,
or the mean gap over those tokens, whichever the pool's limits name: the
edge is held to the widest gap, the cloud to the mean (its routing flips,
a near-tied expert choice that rounding decides, move a few tokens by as
much as the e4m3 control moves its widest; the mean reads them as little
and a lower precision or a fault that moves many tokens as much).
"""
from __future__ import annotations

import numpy as np

from systems import routed_pools
from seeds import jax_key

CLOUD = 1
DECODE_SPAN = "bench.cloud.decode"
#: the cloud pool's calls and the host span each gets in a traced window
CLOUD_SPANS = {"decode_slab": DECODE_SPAN, "prefill_batch": "bench.cloud.prefill",
               "insert_slab": "bench.cloud.insert"}
EXPERT_SCOPE = "moe.experts"


def cloud_group(cfg: dict) -> dict:
    """The configuration with the cloud model as a group of its own
    (``cloud_model``), where ``routed_pools`` reads it: the file holds
    Moonlight's published configuration, as run, at its top level, so the
    group is the top level's plain values."""
    if "cloud_model" in cfg:
        return cfg
    return dict(cfg, cloud_model={k: v for k, v in cfg.items()
                                  if not isinstance(v, (dict, list))})


def moonlight_config(name: str, c: dict):
    """The program's ModelConfig for a Moonlight (DeepSeek-V3) group."""
    from repro.models.config import MLAConfig, ModelConfig, MoEConfig

    want = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "topk_method": "noaux_tc",
            "scoring_func": "sigmoid", "moe_layer_freq": 1,
            "hidden_act": "silu", "attention_bias": False,
            "tie_word_embeddings": False}
    for k, v in want.items():
        if c[k] != v:
            raise ValueError(f"{name}: {k} = {c[k]!r}; the program serves "
                             f"{v!r} only")
    mla = MLAConfig(kv_lora_rank=c["kv_lora_rank"],
                    qk_nope_head_dim=c["qk_nope_head_dim"],
                    qk_rope_head_dim=c["qk_rope_head_dim"],
                    v_head_dim=c["v_head_dim"])
    return ModelConfig(
        name=name, family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=mla.qk_head_dim,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        layer_pattern=("mla",), first_k_dense=c["first_k_dense_replace"],
        mla=mla,
        moe=MoEConfig(num_experts=c["router_experts"],
                      top_k=c["num_experts_per_tok"],
                      d_expert=c["moe_intermediate_size"], scoring="sigmoid",
                      routed_scale=float(c["routed_scaling_factor"]),
                      n_shared=c["n_shared_experts"],
                      n_held=c["n_routed_experts"],
                      expert_offset=c["expert_offset"]),
        compute_dtype=c["compute_dtype"], param_dtype=c["torch_dtype"])


def ref_config(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size", "n_routed_experts",
            "router_experts", "expert_offset", "n_shared_experts",
            "num_experts_per_tok", "routed_scaling_factor",
            "first_k_dense_replace", "num_hidden_layers", "vocab_size",
            "rms_norm_eps", "rope_theta", "initializer_range",
            "score_bias_std")
    return tuple((k, c[k]) for k in keys)


def references():
    """{tier: (reference module, its configuration tuple from a group)}."""
    from ref import moonlight_ref, qwen_ref

    return {0: (qwen_ref, routed_pools.ref_config),
            CLOUD: (moonlight_ref, ref_config)}


def pool_weights(pool, ref, rc, key) -> None:
    """Give a built pool the benchmark's weights (``ref.make_weights``,
    drawn from ``key`` in one jitted call, each leaf in the type the pool
    keeps it in), in place of the pool's own draw, which is dropped first
    so that one copy is held at a time."""
    import jax

    held = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), pool.params)
    want = jax.tree_util.tree_map(
        lambda w, h: jax.ShapeDtypeStruct(w.shape, h.dtype),
        ref.weight_shapes(rc), held)
    if want != held:
        raise ValueError(f"the {pool.name} pool's parameter tree is not the "
                         f"reference's: {held} != {want}")
    dtypes = jax.tree_util.tree_map(lambda h: h.dtype, held)
    pool.params = None
    pool.params = ref.make_weights(rc, key, dtypes)
    jax.block_until_ready(pool.params)


def build_pools(cfg: dict, seed: int) -> dict:
    from repro.serving.pools import ModelPool

    make = {0: routed_pools.model_config, CLOUD: moonlight_config}
    cfg = cloud_group(cfg)
    slots = cfg["serving"]["slots"]
    refs = references()
    pools = {}
    for tier, (name, c) in routed_pools.pool_groups(cfg).items():
        pools[tier] = ModelPool(make[tier](name, c),
                                jax_key(seed, f"pool.{name}"), name=name,
                                n_slots=int(slots[name]))
        ref, rc = refs[tier]
        pool_weights(pools[tier], ref, rc(c), jax_key(seed, f"weights.{name}"))
    return pools


def _spanned(fn, span, name):
    def call(*a, **kw):
        with span(name):
            return fn(*a, **kw)
    return call


class Cell(routed_pools.Cell):
    def __init__(self, cfg: dict, mix: dict, seed: int, *, counter):
        cfg = cloud_group(cfg)
        super().__init__(cfg, mix, seed, counter=counter,
                         pools=build_pools(cfg, seed))

    def _cloud(self):
        return self.session.executor.execs[CLOUD]

    def window(self, seconds: float, span):
        import jax

        from repro.serving.pools import ModelPool

        pex = self._cloud()
        pool = pex.pool
        self.traced = span is jax.profiler.TraceAnnotation
        if self.traced:
            for call, name in CLOUD_SPANS.items():
                setattr(pool, call, _spanned(getattr(pool, call), span, name))
        before = ModelPool.counters(pex.slab)
        try:
            rec = super().window(seconds, span)
        finally:
            for call in CLOUD_SPANS:
                pool.__dict__.pop(call, None)
        after = ModelPool.counters(pex.slab)
        rec.extra["expert_counters"] = {k: after[k] - before[k] for k in after}
        rec.extra["decode_steps_cloud"] = rec.extra["decode_steps"][CLOUD]
        self.rec = rec
        return rec

    def _decode_hlo(self) -> str:
        pex = self._cloud()
        return pex.pool._decode_slab.lower(
            pex.pool.params, pex.slab, pex.last_ids).compile().as_text()

    def free(self):
        # the pool is held by no local here when the base class drops the
        # session, so that its collection frees the device for the reference
        if getattr(self, "traced", False):
            self.rec.extra["cloud_decode_hlo"] = self._decode_hlo()
        super().free()

    def check(self, rec) -> dict:
        from systems.router import compare, reference

        if rec.trace is not None:
            report_cloud_share(rec.trace)
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
        for tier, (name, c) in routed_pools.pool_groups(self.cfg).items():
            rows = self.sample(tier)
            if not rows:
                out[f"{name}_unchecked"] = {"value": 1, "limit": 0}
                continue
            out.update(compared(name, logit_gaps(tier, c, self.seed, name,
                                                 rows), lim))
        return out


def report_cloud_share(t) -> None:
    """The cloud pool's share of the device's busy time in the traced
    window (device time inside the cloud's spans), on standard error."""
    import sys

    from program_trace import intersect
    from xplane import covered, union

    names = set(CLOUD_SPANS.values())
    spans = union((s.start, s.end) for s in t.spans if s.name in names)
    busy = union((ev.start, ev.end) for ev in t.ops[0]) if t.ops else []
    if not spans or not busy:
        return
    inside = intersect(spans, busy)
    print(f"bench: cloud pool device time {inside * 1e-9:.4f} s of busy "
          f"{covered(busy) * 1e-9:.4f} s "
          f"({100.0 * inside / covered(busy):.2f}%)", file=sys.stderr,
          flush=True)


def reference_logits(tier: int, c: dict, seed: int, name: str, rows,
                     fp8=False):
    """Reference logits at every served token's position, per row:
    [(row, (decoded, V) logits)], weights made anew from the seed."""
    import jax
    import jax.numpy as jnp

    ref, rc = references()[tier]
    rc = rc(c)
    params = ref.make_weights(rc, jax_key(seed, f"weights.{name}"))
    out = []
    batch = 8
    for i in range(0, len(rows), batch):
        part = rows[i:i + batch]
        seqs = []
        for k, _, stream, n_prompt, ids in part:
            prompt = (stream * 131 + np.arange(n_prompt)) % c["vocab_size"]
            seqs.append(np.concatenate([prompt, ids[:-1]]).astype(np.int32))
        width = max(len(s) for s in seqs)
        toks = np.zeros((len(seqs), width), np.int32)
        pick = np.zeros((len(seqs), len(part[0][4])), np.int32)
        for j, (s, row) in enumerate(zip(seqs, part)):
            toks[j, :len(s)] = s
            pick[j] = row[3] - 1 + np.arange(len(row[4]))
        lg = ref.logits(rc, params, jnp.asarray(toks), jnp.asarray(pick),
                        fp8=fp8)
        lg = np.asarray(jax.device_get(lg))
        out += [(row, lg[j]) for j, row in enumerate(part)]
    del params
    return out


def compared(name: str, gaps: tuple, lim: dict) -> dict:
    """The pool's numbers that its limits name: ``<name>_logit_gap`` (the
    widest gap) and ``<name>_logit_gap_mean``; both go to standard error."""
    import sys

    values = dict(zip((f"{name}_logit_gap", f"{name}_logit_gap_mean"), gaps))
    print(f"bench: {name} pool: widest logit gap {gaps[0]!r}, mean "
          f"{gaps[1]!r}", file=sys.stderr, flush=True)
    return {k: {"value": v, "limit": lim[k]} for k, v in values.items()
            if k in lim}


def _gaps(ref, chosen) -> tuple:
    """(widest, mean) over every position of the gap between the
    reference's best logit and that of the token chosen there."""
    gaps = np.concatenate([lg.max(axis=-1) - lg[np.arange(len(ids)), ids]
                           for (_, lg), ids in zip(ref, chosen)])
    return float(gaps.max()), float(gaps.mean())


def logit_gaps(tier: int, c: dict, seed: int, name: str, rows) -> tuple:
    """(widest, mean), over every served token of ``rows``, of the gap
    between the reference's best logit at its position and the served
    token's.  The mean reads an expert model's rare routing flips (a
    near-tied expert choice that rounding decides, moving one token by
    much) as little, and a fault or a lower precision that moves many
    tokens as much."""
    ref = reference_logits(tier, c, seed, name, rows)
    return _gaps(ref, [row[4] for row, _ in ref])


def control(cell, rec) -> dict:
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
    for tier, (name, c) in routed_pools.pool_groups(cell.cfg).items():
        rows = cell.sample(tier)
        ref = reference_logits(tier, c, cell.seed, name, rows)
        low = reference_logits(tier, c, cell.seed, name, rows, fp8=True)
        out.update(compared(name, _gaps(ref, [lq.argmax(axis=-1)
                                              for _, lq in low]), lim))
    return out


def expert_device_s(rec) -> float | None:
    """Device seconds, in a traced window, of the cloud decode programs'
    operations under the program's ``moe.experts`` scope: operations
    matched to the scope through the compiled decode program's HLO text
    (``op_name`` paths) and to the cloud's decode steps through the
    ``bench.cloud.decode`` spans (busy union, averaged over the device
    planes).  None where there is nothing to read."""
    import bisect

    from program_trace import hlo_op_names
    from xplane import covered, op_name, union

    t, hlo = rec.trace, rec.extra.get("cloud_decode_hlo")
    if t is None or not hlo:
        return None
    scoped = {k for k, v in hlo_op_names(hlo).items()
              if EXPERT_SCOPE in v.split("/")}
    spans = union((s.start, s.end) for s in t.spans if s.name == DECODE_SPAN)
    if not scoped or not spans:
        return None
    starts = [s for s, _ in spans]

    def inside(ev):
        i = bisect.bisect_right(starts, ev.start) - 1
        return i >= 0 and ev.end <= spans[i][1]

    per = [covered((ev.start, ev.end) for ev in ops
                   if op_name(ev.name).lstrip("%") in scoped and inside(ev))
           for ops in t.ops]
    if not any(per):
        return None
    return sum(per) / len(per) * 1e-9
