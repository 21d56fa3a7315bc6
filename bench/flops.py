"""Model operations of serving, counted from a model configuration's shapes,
and the chip's peaks.

A multiply-add is 2 operations.  Per token through one decoder layer:
the q, k, v and output projections, the SwiGLU MLP's three matrices, and
attention's scores and weighted values over the positions the token attends
to.  The output head runs once per generated token: a prompt's last
position (whose logits give the first token) and each decode step.
Norms, rotary embedding and softmax are not counted.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, what: str = "bf16_flops") -> float:
    """The chip's published peak; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind][what])


def layer_matmul_flops(c: dict) -> float:
    """Per token per layer, projections and MLP."""
    d, h, kv, hd = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"], c["head_dim"]
    qkvo = d * hd * (2 * h + 2 * kv)
    mlp = 3 * d * c["intermediate_size"]
    return 2.0 * (qkvo + mlp)


def attention_flops(c: dict, context: int) -> float:
    """Per token per layer attending to ``context`` positions."""
    return 2.0 * 2 * c["num_attention_heads"] * c["head_dim"] * context


def head_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def request_flops(c: dict, prompt: int, decoded: int) -> float:
    """One served request: the prompt's prefill (token i attends to i + 1
    positions) with the head at its last position, then ``decoded - 1``
    decode steps, step j attending to ``prompt + j + 1`` positions."""
    n = c["num_hidden_layers"]
    total = prompt * n * layer_matmul_flops(c)
    total += n * sum(attention_flops(c, i + 1) for i in range(prompt))
    total += head_flops(c)
    for j in range(decoded - 1):
        total += n * (layer_matmul_flops(c)
                      + attention_flops(c, prompt + j + 1)) + head_flops(c)
    return total
