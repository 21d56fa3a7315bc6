"""The one traffic generator.  A mix is a data file under ``bench/traffic/``
(``<mix>.json``) that this module reads; every draw comes from ``--seed``.

A mix gives, per round of camera segments:

* ``difficulty``: content difficulty z ~ clip(Beta(a, b) * scale, lo, hi)
  and ``requirement``: accuracy floors A^q ~ U[lo, hi] -- the laws of the
  program's round sampler (paper §4.1.2: stable U[0.6, 0.7], fluctuating
  U[0.5, 0.8]).  Each round draws them stratified (one draw in each of M
  equal slices of probability, shuffled over the cameras), so every round
  of every seed holds the same spread of work in another order;
* motion features dx ~ N(0, 1) of the gate's width, one vector per camera;
* ``telemetry`` (optional): per-round capacity telemetry ``bw_scale`` on the
  whole uplink from a Gilbert-Elliott chain (good -> bad with
  ``p_good_to_bad``, bad -> good with ``p_bad_to_good``, ``bad_scale`` of
  capacity when bad).  The chain is drawn until it holds exactly
  ``bad_rounds`` bad rounds, so every seed plans against the same amount of
  congestion, in another order;
* the loop: ``closed`` (the next round starts when the last is served) or
  ``open`` (round k is due at ``k * round_period_s`` from the window's
  start, whether or not the system kept up).

``bank_rounds`` rounds are drawn before the window and cycled.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from seeds import np_rng


@dataclass
class RoundBank:
    z: np.ndarray               # (B, M) float32 difficulty
    aq: np.ndarray              # (B, M) float32 accuracy floors
    dx: np.ndarray              # (B, M, d) float32 motion features
    bw_scale: np.ndarray | None  # (B,) float32 capacity telemetry

    @property
    def rounds(self) -> int:
        return self.z.shape[0]


def stratified(rng, n: int) -> np.ndarray:
    """n uniforms, one in each slice [i/n, (i+1)/n), in random order."""
    return rng.permutation((np.arange(n) + rng.random(n)) / n)


def difficulty(rng, n, spec) -> np.ndarray:
    from scipy.stats import beta

    lo, hi = spec["clip"]
    z = beta.ppf(stratified(rng, n), spec["beta_a"], spec["beta_b"]) \
        * spec["scale"]
    return np.clip(z, lo, hi).astype(np.float32)


def requirement(rng, n, lo_hi) -> np.ndarray:
    lo, hi = lo_hi
    return (lo + (hi - lo) * stratified(rng, n)).astype(np.float32)


def gilbert_elliott(rng, n: int, p_gb: float, p_bg: float, bad_scale: float,
                    bad_rounds: int) -> np.ndarray:
    """(n,) capacity fractions from a two-state chain started in its
    stationary law, redrawn until exactly ``bad_rounds`` rounds are bad."""
    if not 0 <= bad_rounds <= n:
        raise ValueError(f"bad_rounds {bad_rounds} outside 0..{n}")
    stationary_bad = p_gb / (p_gb + p_bg)
    while True:
        bad = np.zeros(n, bool)
        state = rng.random() < stationary_bad
        for t in range(n):
            bad[t] = state
            flip = rng.random()
            state = (flip >= p_bg) if state else (flip < p_gb)
        if bad.sum() == bad_rounds:
            return np.where(bad, bad_scale, 1.0).astype(np.float32)


def round_bank(mix: dict, cameras: int, d_feature: int, seed: int) -> RoundBank:
    b = int(mix["bank_rounds"])
    rng = np_rng(seed, "traffic.rounds")
    z = np.stack([difficulty(rng, cameras, mix["difficulty"])
                  for _ in range(b)])
    aq = np.stack([requirement(rng, cameras, mix["requirement"])
                   for _ in range(b)])
    dx = np_rng(seed, "traffic.features").standard_normal(
        (b, cameras, d_feature), np.float32)
    tel = mix.get("telemetry")
    bw_scale = None
    if tel is not None:
        bw_scale = gilbert_elliott(
            np_rng(seed, "traffic.telemetry"), b, tel["p_good_to_bad"],
            tel["p_bad_to_good"], tel["bad_scale"], tel["bad_rounds"])
    return RoundBank(z=z, aq=aq, dx=dx, bw_scale=bw_scale)


def due_time(mix: dict, k: int) -> float:
    """Seconds after the window's start at which open-loop round k is due."""
    return k * float(mix["round_period_s"])
