"""The ``force=`` pin shared by every kernel's ``ops.py`` entry point."""
from __future__ import annotations

import jax

FORCES = ("auto", "ref", "pallas", "interpret")


def pallas_interpret(force: str):
    """How an ops wrapper runs under ``force``: ``None`` = the jnp ref,
    ``False`` = the compiled Pallas kernel, ``True`` = the Pallas interpreter.

    "auto" compiles the kernel on a TPU and runs the ref anywhere else;
    "pallas" always compiles (and fails where Mosaic has no TPU), so a run
    that asks for the kernel never silently interprets; "interpret" is the
    explicit request the CPU parity tests make.
    """
    if force not in FORCES:
        raise ValueError(f"force must be one of {FORCES}, got {force!r}")
    if force == "ref" or (force == "auto" and jax.default_backend() != "tpu"):
        return None
    return force == "interpret"
