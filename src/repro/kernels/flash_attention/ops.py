"""jit'd public wrapper: dispatches the compiled Pallas kernel on TPU and
the jnp ref elsewhere (``repro.kernels.dispatch``)."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels.flash_attention.kernel import flash_attention as _pallas
from repro.kernels.flash_attention.ref import attention_ref as _ref
from repro.kernels.dispatch import pallas_interpret


@partial(jax.jit, static_argnames=("window", "causal", "block_q", "block_k", "force"))
def flash_attention(q, k, v, *, window: Optional[int] = None, causal: bool = True,
                    block_q: int = 128, block_k: int = 128, force: str = "auto"):
    interpret = pallas_interpret(force)
    if interpret is not None:
        return _pallas(q, k, v, window=window, causal=causal,
                       block_q=block_q, block_k=block_k,
                       interpret=interpret)
    return _ref(q, k, v, window=window, causal=causal)
