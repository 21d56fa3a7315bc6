"""Model pools: the edge/cloud tiers as live JAX serving endpoints.

A pool owns one model variant (params + jit'd prefill/decode) and a request
queue; the R2E-VID router's (route, v) decision maps a segment's token
workload to a pool.  At production scale each pool is a TP slice of the
fleet; here pools run reduced variants on the host mesh so examples/tests
exercise the real code path end-to-end.

Two serving surfaces:

* :meth:`ModelPool.serve_segment` — the original serial path (one prefill +
  an eager decode loop per batch); retained as the parity oracle for the
  continuous-batching executor.
* the **cache-slot slab** entry points (:meth:`make_slab`,
  :meth:`prefill_batch`, :meth:`insert_slab`, :meth:`decode_slab`) — the
  building blocks :mod:`repro.serving.dispatch` schedules: a fixed slab of
  ``n_slots`` KV-cache rows with *per-slot* progress (the model's decode
  path accepts a ``(B,)`` length vector), so concurrent segments join and
  leave the decode batch between steps and cache slots are reused in place.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import Ctx, cache_specs, decode_step, model_specs, prefill
from repro.models.config import ModelConfig
from repro.models.params import init_params, tree_map_specs
from repro.runtime.straggler import p99_jnp


@dataclasses.dataclass
class PoolStats:
    """Counters plus per-request latency samples.

    ``latencies`` holds one sojourn sample (seconds, enqueue→finish; the
    serial path has no queue so its samples are batch wall time) per served
    request; the derived quantiles reuse the straggler toolkit's
    ``p99_jnp`` so serving and realization report tails the same way.
    """
    requests: int = 0
    tokens: int = 0
    busy_s: float = 0.0
    latencies: list = dataclasses.field(default_factory=list)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / max(self.busy_s, 1e-9)

    def p50_s(self) -> float:
        if not self.latencies:
            return 0.0
        return float(jnp.quantile(
            jnp.asarray(self.latencies, jnp.float32), 0.5))

    def p99_s(self) -> float:
        if not self.latencies:
            return 0.0
        return float(p99_jnp(self.latencies))

    def summary(self) -> dict:
        return {
            "requests": self.requests,
            "tokens": self.tokens,
            "busy_s": self.busy_s,
            "tokens_per_s": self.tokens_per_s,
            "p50_s": self.p50_s(),
            "p99_s": self.p99_s(),
        }


# ---------------------------------------------------------------------------
# Slab primitives (module-level jit so they are shared per (ctx, shapes))
# ---------------------------------------------------------------------------
def _insert_slab_impl(slab, cache, slots):
    """Scatter a prefilled cache's first ``len(slots)`` rows into slab slots.

    Cache leaves under ``segments`` carry the stacked layer axis in front,
    so the request/batch axis is axis 1; a prefill cache's seq axis may be
    shorter than the slab's (shorter prompts) and is zero-padded — padded
    entries sit beyond the slot's length and are masked by decode attention.
    """
    n_real = slots.shape[0]

    def put(sl, cl):
        cl = jax.lax.slice_in_dim(cl, 0, n_real, axis=1)
        pad = [(0, 0)] * cl.ndim
        for ax in range(2, cl.ndim):
            pad[ax] = (0, sl.shape[ax] - cl.shape[ax])
        return sl.at[:, slots].set(jnp.pad(cl, pad))

    segments = jax.tree_util.tree_map(put, slab["segments"],
                                      cache["segments"])
    length = slab["length"].at[slots].set(cache["length"])
    # the slab's own leaves (an expert model's counters) pass through
    return dict(slab, length=length, segments=segments)


_insert_slab = jax.jit(_insert_slab_impl, donate_argnums=(0,))


class ModelPool:
    """``n_slots``: the cache-slot slab this pool decodes over when an
    executor is not told otherwise (``DispatchExecutor``'s ``n_slots``)."""

    def __init__(self, cfg: ModelConfig, rng=None, name: str = "pool",
                 n_slots: int = 16):
        self.cfg = cfg
        self.name = name
        self.n_slots = n_slots
        self.ctx = Ctx(cfg=cfg)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        self.params = init_params(model_specs(cfg), rng)
        self._prefill = jax.jit(lambda p, b: prefill(self.ctx, p, b))
        self._decode = jax.jit(lambda p, c, b: decode_step(self.ctx, p, c, b))
        self._decode_slab = jax.jit(self._decode_slab_impl,
                                    donate_argnums=(1,))
        self.stats = PoolStats()

    def serve_segment(self, tokens, decode_tokens: int = 8):
        """Prefill a token batch then decode a few tokens; returns text ids."""
        t0 = time.perf_counter()
        b, s = tokens.shape
        if b == 0:
            # a fully-drained tier (every routed lane dead/elsewhere) is a
            # legal dispatch, not a crash — serve nothing, touch no stats
            return jnp.zeros((0, decode_tokens), jnp.int32)
        logits, cache = self._prefill(self.params, {"tokens": tokens})
        out = [jnp.argmax(logits, axis=-1)]
        for _ in range(decode_tokens - 1):
            logits, cache = self._decode(self.params, cache, {"tokens": out[-1][:, None]})
            out.append(jnp.argmax(logits, axis=-1))
        jax.block_until_ready(out[-1])
        dt = time.perf_counter() - t0
        self.stats.requests += b
        self.stats.tokens += b * (s + decode_tokens)
        self.stats.busy_s += dt
        self.stats.latencies.extend([dt] * b)
        return jnp.stack(out, axis=1)

    # -- continuous-batching slab entry points ------------------------------
    def make_slab(self, n_slots: int, max_prefill_len: int):
        """A fixed slab of ``n_slots`` cache rows sized for prompts up to
        ``max_prefill_len`` tokens plus the model's decode headroom.  The
        scalar cache ``length`` becomes a ``(n_slots,)`` vector — per-slot
        progress, so rows at different depths co-batch in one decode step.
        An expert model's slab also holds its expert counters
        (``models.model.EXPERT_COUNTERS``), summed over the decode steps
        on the device; :meth:`counters` reads them."""
        specs = cache_specs(self.cfg, n_slots, max_prefill_len)
        slab = tree_map_specs(lambda s: jnp.zeros(s.shape, s.dtype), specs)
        slab["length"] = jnp.zeros((n_slots,), jnp.int32)
        return slab

    @staticmethod
    def counters(slab) -> dict:
        """The slab's expert counters on the host ({} without experts)."""
        return {k: int(v) for k, v in jax.device_get(slab.get("counters", {})).items()}

    def prefill_batch(self, tokens):
        """Prefill one bucketed-length batch.  Returns (first decoded ids
        (B,), the batch's fresh cache) — the ids are the segment's first
        output token, exactly as in :meth:`serve_segment`."""
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, {"tokens": tokens})
        ids = jnp.argmax(logits, axis=-1)
        jax.block_until_ready(ids)
        self.stats.busy_s += time.perf_counter() - t0
        return ids, cache

    def insert_slab(self, slab, cache, slots):
        """Scatter ``cache``'s first ``len(slots)`` rows into ``slab`` at
        the given slot indices (donating the slab buffers).  Rows beyond
        ``len(slots)`` are bucket padding and are dropped."""
        return _insert_slab(slab, cache, jnp.asarray(slots, jnp.int32))

    def _decode_slab_impl(self, params, slab, last_ids):
        logits, slab = decode_step(self.ctx, params, slab,
                                   {"tokens": last_ids[:, None]})
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), slab

    def decode_slab(self, slab, last_ids):
        """One token-level decode step over the WHOLE slab: every slot
        advances by one token against its own cache progress.  Returns
        ((n_slots,) next ids, the updated slab).  Inactive slots compute
        garbage that the executor ignores — the fixed shape is what keeps
        this a single compiled program."""
        t0 = time.perf_counter()
        ids, slab = self._decode_slab(self.params, slab,
                                      jnp.asarray(last_ids, jnp.int32))
        jax.block_until_ready(ids)
        self.stats.busy_s += time.perf_counter() - t0
        return ids, slab


def make_tier_pools(edge_cfg: ModelConfig, cloud_cfg: ModelConfig):
    return {
        0: ModelPool(edge_cfg, jax.random.PRNGKey(1), name="edge"),
        1: ModelPool(cloud_cfg, jax.random.PRNGKey(2), name="cloud"),
    }
