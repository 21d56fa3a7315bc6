"""Gated MLP (SwiGLU / GeGLU)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import Ctx
from repro.models.params import ParamSpec


def mlp_specs(cfg: ModelConfig, width: int = 0) -> dict:
    """SwiGLU weights of width ``width`` (0: the model's d_ff)."""
    d, f = cfg.d_model, width or cfg.d_ff
    s_in = d ** -0.5
    s_out = f ** -0.5 / math.sqrt(2 * cfg.num_layers)
    return {
        "w_gate": ParamSpec((d, f), ("embed", "mlp"), stddev=s_in),
        "w_up": ParamSpec((d, f), ("embed", "mlp"), stddev=s_in),
        "w_down": ParamSpec((f, d), ("mlp", "embed"), stddev=s_out),
    }


def mlp_forward(ctx: Ctx, p, x, activation: str = "silu"):
    dt = ctx.compute_dtype
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    act = jax.nn.gelu if activation == "gelu" else jax.nn.silu
    h = act(g) * u
    h = ctx.constrain(h, "batch", "act_seq", "mlp")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
