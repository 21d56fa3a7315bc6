"""``correct`` at small sizes on the CPU: sound runs pass; the control (the
reference one precision step down, in the program's place) comes out not
correct under the configuration's own limits; and a run whose timed path
is broken underneath comes out not correct -- once for each fault a cell
can have.  The control's readings at the cells' own sizes come from
``bench/control.py`` on the chip."""
import jax
import jax.numpy as jnp
import pytest

import small

ROUTER = "fleet4096.congested"
POOLS = "edgecloud-qwen.live"
BACKLOG = "edgecloud-qwen.backlog"


def _passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def _control_fails(workload: str, seed: int):
    mod, cell, rec = small.cell(workload, seed)
    sound = cell.check(rec)
    assert _passes(sound), sound
    ctl = mod.control(cell, rec)
    assert not _passes(ctl), (ctl, sound)


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 2**33 + 5])
def test_router_control_is_not_correct(seed):
    _control_fails(ROUTER, seed)


def test_pools_control_is_not_correct():
    _control_fails(POOLS, 2**31 + 6)


def test_backlog_control_is_not_correct():
    _control_fails(BACKLOG, 2**32 + 6)


def _flip_first_route(orig):
    def step(self, obs):
        sol = orig(self, obs)
        return dict(sol, route=sol["route"].at[0].set(1 - sol["route"][0]))
    return step


def _frozen_state(orig):
    def step(self, obs):
        kept = jax.tree_util.tree_map(jnp.copy, self.state)
        sol = orig(self, obs)
        self.state = kept
        return sol
    return step


def _half_left_out(orig):
    def step(self, obs):
        sol = orig(self, obs)
        half = sol["route"].shape[0] // 2
        return {k: v.at[half:].set(0) if k in ("route", "r", "p", "v")
                else v for k, v in sol.items()}
    return step


@pytest.mark.parametrize("fault", [_flip_first_route, _frozen_state,
                                   _half_left_out])
def test_router_faults_are_not_correct(monkeypatch, fault):
    from repro.serving.session import ServeSession

    monkeypatch.setattr(ServeSession, "step", fault(ServeSession.step))
    out = small.run(ROUTER, 2**31 + 7)
    assert out["correct"] is False, out["checks"]


def _token_altered(orig):
    def decode_slab(self, slab, last_ids):
        ids, slab = orig(self, slab, last_ids)
        return (ids + 1) % self.cfg.vocab_size, slab
    return "decode_slab", decode_slab


def _slab_unchanged(orig):
    def decode_slab(self, slab, last_ids):
        kept = jax.tree_util.tree_map(jnp.copy, slab)
        ids, _ = orig(self, slab, last_ids)
        return ids, kept
    return "decode_slab", decode_slab


def _edge_slab_unchanged(orig):
    """The edge pool alone returns its slab unchanged; the cloud is sound."""
    def decode_slab(self, slab, last_ids):
        if self.name != "edge":
            return orig(self, slab, last_ids)
        kept = jax.tree_util.tree_map(jnp.copy, slab)
        ids, _ = orig(self, slab, last_ids)
        return ids, kept
    return "decode_slab", decode_slab


def _prefill_half(orig):
    def prefill_batch(self, tokens):
        ids, cache = orig(self, tokens)
        keep = max(1, tokens.shape[0] // 2)
        return ids.at[keep:].set((ids[keep:] + 7) % self.cfg.vocab_size), cache
    return "prefill_batch", prefill_batch


def test_pools_sound_run_is_correct():
    out = small.run(POOLS, 2**31 + 8)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", [_token_altered, _prefill_half,
                                   _slab_unchanged, _edge_slab_unchanged])
def test_pools_faults_are_not_correct(monkeypatch, fault):
    from repro.serving.pools import ModelPool

    name, fn = fault(getattr(ModelPool, fault(lambda *a: None)[0]))
    monkeypatch.setattr(ModelPool, name, fn)
    out = small.run(POOLS, 2**31 + 9)
    assert out["correct"] is False, out["checks"]
    if fault is _edge_slab_unchanged:
        gap = out["checks"]["edge_logit_gap"]
        assert gap["value"] > gap["limit"], out["checks"]
