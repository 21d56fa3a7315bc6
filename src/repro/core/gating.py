"""Temporal gating unit (paper §3.2, Eq. 5-6).

Gated recurrent cell with *content-adaptive forget bias*:

    g_t = σ( W_g Δx_t + U_g h_{t-1} + b_g + α · Var(Δx_{t-T:t}) )      (5)
    r_t = σ( W_r Δx_t + U_r h_{t-1} + b_r )
    h_t = (1-g_t) ⊙ h_{t-1} + g_t ⊙ tanh( W_h Δx_t + U_h (r_t ⊙ h_{t-1}) + b_h )  (6)
    τ_t = σ( W_o h_t + b_o ) ∈ [0,1]      — temporal significance score

The volatility term α·Var(Δx_{t-T:t}) opens the gate aggressively when
recent motion variance spikes (missed-critical-event protection).  Also
provided as a fused Pallas TPU kernel in repro.kernels.temporal_gate.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.temporal_gate.ops import gate_cell
from repro.models.params import ParamSpec


@dataclasses.dataclass(frozen=True)
class GateConfig:
    d_feature: int
    d_hidden: int = 32
    var_window: int = 8          # T in Eq. (5)
    alpha_init: float = 1.0
    # every how many steps the batched gate recomputes its running Σ/Σ² from
    # the exact ring buffer (bounds float32 drift of the incremental
    # volatility).  0 = once per window (var_window); 1 = every step (the
    # incremental sums are then always exact, matching the looped oracle).
    resync_period: int = 0


def gate_specs(cfg: GateConfig) -> dict:
    d, m = cfg.d_feature, cfg.d_hidden
    sd, sm = d ** -0.5, m ** -0.5
    return {
        "w_g": ParamSpec((d, m), (None, None), stddev=sd),
        "u_g": ParamSpec((m, m), (None, None), stddev=sm),
        "b_g": ParamSpec((m,), (None,), init="zeros"),
        "alpha": ParamSpec((), (), init="ones"),
        "w_r": ParamSpec((d, m), (None, None), stddev=sd),
        "u_r": ParamSpec((m, m), (None, None), stddev=sm),
        "b_r": ParamSpec((m,), (None,), init="zeros"),
        "w_h": ParamSpec((d, m), (None, None), stddev=sd),
        "u_h": ParamSpec((m, m), (None, None), stddev=sm),
        "b_h": ParamSpec((m,), (None,), init="zeros"),
        "w_o": ParamSpec((m, 1), (None, None), stddev=sm),
        "b_o": ParamSpec((1,), (None,), init="zeros"),
    }


class GateState(NamedTuple):
    h: jnp.ndarray          # (m,) hidden
    var_buf: jnp.ndarray    # (T, d) recent Δx ring buffer
    var_idx: jnp.ndarray    # scalar int32


def init_state(cfg: GateConfig) -> GateState:
    return GateState(
        h=jnp.zeros((cfg.d_hidden,), jnp.float32),
        var_buf=jnp.zeros((cfg.var_window, cfg.d_feature), jnp.float32),
        var_idx=jnp.zeros((), jnp.int32),
    )


def gate_step(cfg: GateConfig, p, state: GateState, dx):
    """One recurrence step. dx: (d,). Returns (new_state, (tau, g_mean))."""
    buf = jax.lax.dynamic_update_slice_in_dim(
        state.var_buf, dx[None], jnp.mod(state.var_idx, cfg.var_window), axis=0
    )
    # volatility over the last T frames (scalar: mean feature variance)
    vol = jnp.var(buf, axis=0).mean()

    g = jax.nn.sigmoid(dx @ p["w_g"] + state.h @ p["u_g"] + p["b_g"] + p["alpha"] * vol)
    r = jax.nn.sigmoid(dx @ p["w_r"] + state.h @ p["u_r"] + p["b_r"])
    cand = jnp.tanh(dx @ p["w_h"] + (r * state.h) @ p["u_h"] + p["b_h"])
    h = (1.0 - g) * state.h + g * cand
    tau = jax.nn.sigmoid(h @ p["w_o"] + p["b_o"])[0]
    new_state = GateState(h=h, var_buf=buf, var_idx=state.var_idx + 1)
    return new_state, (tau, g.mean())


# ---------------------------------------------------------------------------
# Fused batched streaming step (the serving hot path)
#
# ``gate_step`` re-scans the whole (T, d) ring buffer every step to get the
# volatility Var(Δx_{t-T:t}); at fleet scale that is an O(T·d) read per
# stream per tick.  The batched state below carries running Σx / Σx² over the
# buffer instead, so each step is O(d): subtract the evicted frame, add the
# new one.  The six-matmul cell itself dispatches to the fused Pallas
# ``gate_cell`` on TPU (pure-jnp ref elsewhere) — one VMEM-resident pass for
# the whole (M, d) stream batch.
# ---------------------------------------------------------------------------
class GateBatchState(NamedTuple):
    h: jnp.ndarray          # (M, m) hidden
    var_buf: jnp.ndarray    # (M, T, d) Δx ring buffer (holds the evictees)
    var_idx: jnp.ndarray    # (M,) int32
    var_sum: jnp.ndarray    # (M, d) running Σ Δx over the buffer
    var_sumsq: jnp.ndarray  # (M, d) running Σ Δx² over the buffer


def init_batch_state(cfg: GateConfig, n_streams: int) -> GateBatchState:
    return GateBatchState(
        h=jnp.zeros((n_streams, cfg.d_hidden), jnp.float32),
        var_buf=jnp.zeros((n_streams, cfg.var_window, cfg.d_feature), jnp.float32),
        var_idx=jnp.zeros((n_streams,), jnp.int32),
        var_sum=jnp.zeros((n_streams, cfg.d_feature), jnp.float32),
        var_sumsq=jnp.zeros((n_streams, cfg.d_feature), jnp.float32),
    )


@jax.named_scope("r2e.gate")
def gate_step_batch(cfg: GateConfig, p, state: GateBatchState, dx, *,
                    force: str = "auto"):
    """One fused recurrence step for all streams. dx: (M, d).

    Returns ``(new_state, (tau (M,), g_mean (M,)))`` — the batched equivalent
    of ``vmap(gate_step)`` with the volatility maintained incrementally.
    """
    t = cfg.var_window
    slot = jnp.mod(state.var_idx, t)                              # (M,)
    old = jnp.take_along_axis(state.var_buf, slot[:, None, None], axis=1)[:, 0]
    var_sum = state.var_sum + dx - old                            # (M, d)
    var_sumsq = state.var_sumsq + dx * dx - old * old
    hit = jnp.arange(t)[None, :] == slot[:, None]                 # (M, T)
    buf = jnp.where(hit[:, :, None], dx[:, None, :], state.var_buf)
    # resync the running sums against the exact ring buffer on a configured
    # cadence (default: once per window): the incremental updates random-walk
    # float32 rounding error over long serving runs; the buffer is exact, so
    # this bounds the drift to ``resync_period`` steps at an amortized O(d)
    # cost (streams advance in lockstep, and if they don't, an off-phase
    # resync is still exact).  lax.cond keeps the (T, d) reduction off the
    # trace-hot path on non-resync steps.
    period = cfg.resync_period or t
    var_sum, var_sumsq = jax.lax.cond(
        (state.var_idx[0] + 1) % period == 0,
        lambda: (buf.sum(axis=1), jnp.square(buf).sum(axis=1)),
        lambda: (var_sum, var_sumsq),
    )
    mean = var_sum / t
    vol = jnp.maximum(var_sumsq / t - mean * mean, 0.0).mean(axis=-1)  # (M,)

    h, tau, g_mean = gate_cell(dx, state.h, vol, p, force=force)
    new_state = GateBatchState(
        h=h, var_buf=buf, var_idx=state.var_idx + 1,
        var_sum=var_sum, var_sumsq=var_sumsq,
    )
    return new_state, (tau, g_mean)


def gate_scan(cfg: GateConfig, p, dxs, state: GateState | None = None):
    """dxs: (T, d) -> (taus (T,), gate_means (T,), final_state)."""
    if state is None:
        state = init_state(cfg)

    def body(s, dx):
        s, out = gate_step(cfg, p, s, dx)
        return s, out

    final, (taus, gs) = jax.lax.scan(body, state, dxs)
    return taus, gs, final


def gate_scan_batch(cfg: GateConfig, p, dxs, states=None):
    """dxs: (B, T, d) — vmapped over streams."""
    if states is None:
        states = jax.vmap(lambda _: init_state(cfg))(jnp.arange(dxs.shape[0]))
    return jax.vmap(lambda d, s: gate_scan(cfg, p, d, s))(dxs, states)


def gate_window_scan(cfg: GateConfig, p, dxs, state: GateBatchState | None = None,
                     *, force: str = "auto"):
    """dxs: (M, T, d) -> (taus (M, T), gate_means (M, T), final_state).

    Time-scan of the fused batched streaming step — the whole stream batch
    advances one segment per scan tick through ``gate_step_batch``, so the
    windowed API shares the streaming path's kernel dispatch and O(d)
    incremental volatility instead of vmapping a per-stream ``lax.scan``
    (``gate_scan_batch``, kept for ``gate_loss`` training).
    """
    if state is None:
        state = init_batch_state(cfg, dxs.shape[0])

    def body(s, dx):
        s, out = gate_step_batch(cfg, p, s, dx, force=force)
        return s, out

    final, (taus, gs) = jax.lax.scan(body, state, jnp.moveaxis(dxs, 1, 0))
    return taus.T, gs.T, final


# ---------------------------------------------------------------------------
# Meta-training (offline warm-up): L = L_acc + λ1·L_lat + λ2·L_comp
#   L_acc : BCE of τ against the oracle cloud-benefit label
#   L_lat : mean τ      (cloud offloads cost latency)
#   L_comp: mean gate   (gate openness costs compute)
# Online fine-tuning adds a proximal term μ/2 ||θ - θ_offline||² against
# catastrophic forgetting (paper §3.2).
# ---------------------------------------------------------------------------
def gate_loss(cfg: GateConfig, p, dxs, benefit_labels, lam1=0.05, lam2=0.01,
              anchor=None, mu=0.0):
    taus, gs, _ = gate_scan_batch(cfg, p, dxs)
    eps = 1e-6
    bce = -(benefit_labels * jnp.log(taus + eps)
            + (1 - benefit_labels) * jnp.log(1 - taus + eps)).mean()
    l_lat = taus.mean()
    l_comp = gs.mean()
    loss = bce + lam1 * l_lat + lam2 * l_comp
    if anchor is not None and mu > 0:
        prox = sum(
            jnp.sum(jnp.square(a - b))
            for a, b in zip(jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(anchor))
        )
        loss = loss + 0.5 * mu * prox
    return loss, {"bce": bce, "l_lat": l_lat, "l_comp": l_comp}
