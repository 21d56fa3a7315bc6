"""``edgecloud-moonlight.strict`` at a small size on the CPU: a sound run is
correct and counts its experts' rows; the control (the references one
precision step down, in the program's place) is not correct under the
configuration's own limits; and a cloud pool whose expert layer is
broken underneath -- experts held at the wrong offset, the selection bias
used in the weights, the shared experts dropped, a capacity that drops
slots -- serves tokens whose ``cloud_logit_gap_mean`` exceeds its limit."""
import dataclasses

import jax
import numpy as np
import pytest

import harness
import small

WORKLOAD = "edgecloud-moonlight.strict"


def _cloud(c):
    # every width cut to a CPU test's size; 4 of 16 experts held, as 16 of
    # 64 are at full size; initializer_range 0.1 at width 64 as small.py
    return dict(c, hidden_size=64, intermediate_size=96,
                num_attention_heads=4, num_key_value_heads=4,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, moe_intermediate_size=32, n_routed_experts=4,
                router_experts=16, expert_offset=4, num_hidden_layers=3,
                vocab_size=256, initializer_range=0.1)


def spec():
    cfg = harness.system("routed_moe_pools").cloud_group(
        harness.config("edgecloud-moonlight"))
    dep = dict(cfg["deployment"], resolutions=[360, 1080], fps_options=[10, 50])
    cfg = dict(cfg, cloud_model=_cloud(cfg["cloud_model"]),
               edge_model=small._model(cfg["edge_model"]), deployment=dep)
    mix = dict(harness.traffic("strict"), cameras=10, bank_rounds=4,
               check_segments=64, max_warmup_rounds=2)
    config, traffic = WORKLOAD.split(".")
    return ({"name": WORKLOAD, "config": config, "traffic": traffic,
             "chips": 1}, cfg, mix)


def _passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def test_sound_run_is_correct_and_the_control_is_not():
    _, cfg, mix = spec()
    mod = harness.system(cfg["system"])
    cell = mod.Cell(cfg, mix, 2**31 + 21, counter=harness.CompileCounter())
    assert cell.session.executor.execs[1].n_slots == 64
    rec = cell.window(1.0, harness.spans(False))
    cell.free()
    sound = cell.check(rec)
    assert _passes(sound) and rec.failed == 0, sound
    counts = rec.extra["expert_counters"]
    steps = rec.extra["decode_steps_cloud"]
    # each cloud decode step runs 64 slots x 6 choices through 2 expert
    # layers; a quarter of the experts are held here
    assert 0 < counts["expert_rows"] <= steps * 64 * 6 * 2
    assert 0 < counts["experts_hit"] <= steps * 4 * 2
    ctl = mod.control(cell, rec)
    assert not _passes(ctl), (ctl, sound)


def _wrong_offset(moe):
    orig = moe._held

    def held(cfg, flat_ids):
        e = cfg.moe
        return orig(dataclasses.replace(cfg, moe=dataclasses.replace(
            e, expert_offset=e.expert_offset + e.held)), flat_ids)
    return "_held", held


def _bias_in_weights(moe):
    orig = moe.route

    def route(cfg, p, x, dt):
        w, ids, logits = orig(cfg, p, x, dt)
        biased = jax.nn.sigmoid(logits) + p["router_bias"]
        w = jax.numpy.take_along_axis(biased, ids, axis=-1)
        w = w / w.sum(-1, keepdims=True) * cfg.moe.routed_scale
        return w, ids, logits
    return "route", route


def _shared_dropped(moe):
    return "mlp_forward", lambda ctx, p, x, activation="silu": x * 0


def _capacity_drop(moe):
    def dropless(ctx, p, xt, w, ids):
        cfg = dataclasses.replace(ctx.cfg, moe=dataclasses.replace(
            ctx.cfg.moe, capacity_factor=1.25, min_capacity=1))
        y = moe._capacity_dispatch(dataclasses.replace(ctx, cfg=cfg),
                                   p, xt, w, ids)
        return y, jax.numpy.zeros((2,), jax.numpy.int32)
    return "_dropless", dropless


#: the cell's own share: 4 of 16 experts held, the drawn bias small
SHARE = {}
#: every expert held and a bias that dominates the scores: nearly every
#: token goes to the same experts (so capacity binds), and weights taken
#: from the biased scores differ from the unbiased ones by far more than
#: rounding does
CONCENTRATED = {"n_routed_experts": 16, "expert_offset": 0,
                "score_bias_std": 3.0}


@pytest.mark.parametrize("fault,data", [
    (None, SHARE), (None, CONCENTRATED), (_wrong_offset, SHARE),
    (_bias_in_weights, CONCENTRATED), (_shared_dropped, SHARE),
    (_capacity_drop, CONCENTRATED)],
    ids=["sound", "sound-concentrated", "wrong-offset", "bias-in-weights",
         "shared-dropped", "capacity-drop"])
def test_expert_layer_faults_are_not_correct(monkeypatch, fault, data):
    """The cloud pool alone, served through the dispatch executor's slab
    path at the cell's slot count (more requests than slots, so every slot
    serves one) and checked as the cell checks it: sound within the limit,
    each fault over it, on data where the fault acts on every token."""
    from repro.models import moe
    from repro.serving.dispatch import DispatchExecutor, Request

    if fault is not None:
        monkeypatch.setattr(moe, *fault(moe))
    _, cfg, _ = spec()
    c = dict(cfg["cloud_model"], **data)
    cfg = dict(cfg, cloud_model=c)
    mod = harness.system(cfg["system"])
    seed = 2**31 + 22
    pool = mod.build_pools(cfg, seed)[1]
    ex = DispatchExecutor({1: pool}, max_prefill_len=32)
    reqs = [Request(stream=i, tier=1, decode_tokens=8,
                    tokens=((i * 131 + np.arange(16 * (1 + i % 2)))
                            % c["vocab_size"]).astype(np.int32))
            for i in range(96)]
    ex.serve(reqs)
    rows = [(0, 1, x.stream, x.n_prefill, x.ids)
            for x in ex.execs[1].completions]
    checks = mod.compared("cloud", mod.logit_gaps(1, c, seed, "cloud", rows),
                          cfg["limits"])
    over = [v["value"] > v["limit"] for v in checks.values()]
    assert checks and ((not any(over)) if fault is None else any(over)), checks
