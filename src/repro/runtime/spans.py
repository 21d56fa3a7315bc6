"""Host spans of the program, written into the JAX profiler's own trace.

A span is a ``jax.profiler.TraceAnnotation`` (a profiler TraceMe event): it
lands on the host plane of the same ``.xplane.pb`` as the device operations,
on the profiler's one clock, so no second clock or exporter is needed.  The
program names its spans ``r2e.*``:

  ``r2e.step``    the body of ``ServeSession.step``
  ``r2e.launch``  the jitted decide call in ``ServeSession.route``: argument
                  flattening, the observation's transfer, PJRT ``Execute``
                  and the output buffers' allocation, whose runtime events
                  nest inside it on the same thread line

The model pools' programs carry named scopes (``jax.named_scope``, in the
compiled HLO's ``op_name`` paths, so a device trace's operations map to
them through the program's HLO text):

  ``mla.attend``   latent attention (``models/mla.py``): projections, the
                   latent cache write, scores and values
  ``moe.route``    the expert router: scores, selection, weights
  ``moe.experts``  the held experts' compute: dispatch, the grouped
                   matmuls (``ragged_dot`` when serving), combine
  ``moe.shared``   the shared experts

and an expert model's decode cache holds two device-side counters
(``models.model.EXPERT_COUNTERS``), summed over decode steps and layers
and read once when wanted (``ModelPool.counters``), with no host sync per
step:

  ``expert_rows``  (token, choice) rows the held experts computed
  ``experts_hit``  held experts, per layer and step, that got any row

Spans are off by default; off, :func:`span` is one flag test returning a
shared no-op context.  An operator turns them on inside a profiled window::

    with jax.profiler.trace(log_dir):
        spans.enable(True)
        ...                      # serve rounds
        spans.enable(False)
"""
from __future__ import annotations

import contextlib

import jax

_OFF = contextlib.nullcontext()
_on = False


def enable(on: bool) -> None:
    """Turn the program's spans on or off (process-wide)."""
    global _on
    _on = bool(on)


def enabled() -> bool:
    return _on


def span(name: str):
    """A host span named ``name`` when spans are on, a shared no-op else."""
    if not _on:
        return _OFF
    return jax.profiler.TraceAnnotation(name)
