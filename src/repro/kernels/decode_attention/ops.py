"""jit'd public wrapper: dispatches the compiled Pallas kernel on TPU and
the jnp ref elsewhere (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.decode_attention.kernel import decode_attention as _pallas
from repro.kernels.decode_attention.ref import decode_attention_ref as _ref
from repro.kernels.dispatch import pallas_interpret


@partial(jax.jit, static_argnames=("block_s", "force"))
def decode_attention(q, k_cache, v_cache, length, *, block_s: int = 256,
                     force: str = "auto"):
    interpret = pallas_interpret(force)
    if interpret is not None:
        return _pallas(q, k_cache, v_cache, length, block_s=block_s,
                       interpret=interpret)
    return _ref(q, k_cache, v_cache, length)
