"""Cluster runtime simulation: node failures, heartbeats, elastic re-mesh.

On real hardware these events come from the TPU runtime / GKE; here the
injector raises ``NodeFailure`` at scheduled steps and ``elastic_remesh``
rebuilds the largest rectangular mesh from the surviving node count — the
trainer then restores the latest checkpoint with the *new* shardings
(``checkpoint.restore`` device_puts onto the target mesh), which is exactly
the production recovery path.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax

from repro.sharding.compat import make_mesh
from repro.train.trainer import NodeFailure


@dataclasses.dataclass
class FailureInjector:
    """Raise NodeFailure when the trainer reaches a scheduled step."""
    schedule: Dict[int, str]  # step -> failure description
    fired: set = dataclasses.field(default_factory=set)

    def __call__(self, step: int):
        if step in self.schedule and step not in self.fired:
            self.fired.add(step)
            raise NodeFailure(f"step {step}: {self.schedule[step]}")


def elastic_remesh(n_devices: Optional[int] = None, *, min_model: int = 1,
                   prefer: str = "model"):
    """Largest (data, model) mesh from the surviving devices.

    ``prefer="model"`` (default, trainer recovery) keeps the model axis as
    large as possible — TP degree is bounded by what the weights were
    sharded for — and puts the remainder on data.  ``prefer="data"``
    (serving recovery) puts every surviving device on the data axis: serve
    streams shard along data only, so a survivor mesh of shape (n, 1) keeps
    all of them routing.
    """
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    if n <= 0:
        raise ValueError(
            f"elastic_remesh needs at least one surviving device, got "
            f"n_devices={n_devices!r}")
    if prefer not in ("model", "data"):
        raise ValueError(f"prefer must be 'model' or 'data', got {prefer!r}")
    n = min(n, len(devs))
    if prefer == "data":
        model = max(min_model, 1)
        if n % model != 0:
            raise ValueError(
                f"{n} surviving devices not divisible by min_model={model}")
    else:
        model = 1
        for cand in (16, 8, 4, 2, 1):
            if cand <= n and n % cand == 0 and cand >= min_model:
                model = cand
                break
    data = n // model
    return make_mesh((data, model), ("data", "model"), devices=devs[:n])


class ClusterSim:
    """Tracks node liveness via heartbeats; feeds the elastic controller."""

    def __init__(self, n_nodes: int, heartbeat_timeout: float = 3.0):
        self.n_nodes = n_nodes
        self.timeout = heartbeat_timeout
        self.last_seen = {i: 0.0 for i in range(n_nodes)}
        self.dead: set[int] = set()
        self.clock = 0.0

    def tick(self, dt: float = 1.0, heartbeats: Optional[set] = None):
        self.clock += dt
        for i in (heartbeats if heartbeats is not None else set(range(self.n_nodes))):
            if i not in self.dead:
                self.last_seen[i] = self.clock
        newly_dead = {
            i for i in range(self.n_nodes)
            if i not in self.dead and self.clock - self.last_seen[i] > self.timeout
        }
        self.dead |= newly_dead
        return newly_dead

    def kill(self, node: int):
        self.dead.add(node)

    @property
    def alive(self) -> int:
        return self.n_nodes - len(self.dead)
