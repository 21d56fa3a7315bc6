"""Model operations and bytes of serving, counted from a model
configuration's shapes, and the chip's peaks.

A multiply-add is 2 operations.  Per token through one decoder layer:
the q, k, v and output projections, the SwiGLU MLP's three matrices, and
attention's scores and weighted values over the positions the token attends
to.  The output head runs once per generated token: a prompt's last
position (whose logits give the first token) and each decode step.
Norms, rotary embedding and softmax are not counted.

Bytes are what a decode step must read at the published bfloat16, whatever
the program stores: every layer's projection and MLP weights and the output
head (the token embedding where the model ties them) once a step, and the
keys and values of every position each segment attends to.  The embedding
rows a step gathers (one per token), norms and biases are not counted.
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


#: bytes of a published bfloat16 weight or cache entry
BF16 = 2


def layer_params(c: dict) -> int:
    """Weights of one layer's projections and MLP."""
    d, h, kv, hd = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"], c["head_dim"]
    return d * hd * (2 * h + 2 * kv) + 3 * d * c["intermediate_size"]


def layer_matmul_flops(c: dict) -> float:
    """Per token per layer, projections and MLP."""
    return 2.0 * layer_params(c)


def attention_flops(c: dict, context: int) -> float:
    """Per token per layer attending to ``context`` positions."""
    return 2.0 * 2 * c["num_attention_heads"] * c["head_dim"] * context


def head_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def decode_flops(c: dict, prompt: int, decoded: int) -> float:
    """The ``decoded - 1`` decode steps of one served request, step j
    attending to ``prompt + j + 1`` positions."""
    n = c["num_hidden_layers"]
    return sum(n * (layer_matmul_flops(c) + attention_flops(c, prompt + j + 1))
               + head_flops(c) for j in range(decoded - 1))


def request_flops(c: dict, prompt: int, decoded: int) -> float:
    """One served request: the prompt's prefill (token i attends to i + 1
    positions) with the head at its last position, then its decode steps."""
    n = c["num_hidden_layers"]
    total = prompt * n * layer_matmul_flops(c)
    total += n * sum(attention_flops(c, i + 1) for i in range(prompt))
    total += head_flops(c)
    return total + decode_flops(c, prompt, decoded)


def decode_weight_bytes(c: dict) -> int:
    """Weights one decode step reads: every layer's projections and MLP,
    and the output head."""
    head = c["hidden_size"] * c["vocab_size"]
    return BF16 * (c["num_hidden_layers"] * layer_params(c) + head)


def kv_bytes_per_position(c: dict) -> int:
    """Keys and values of one cached position, over every layer."""
    return BF16 * 2 * c["num_hidden_layers"] * c["num_key_value_heads"] \
        * c["head_dim"]


def decode_bytes(c: dict, steps: int, requests) -> int:
    """Bytes ``steps`` decode steps of one model must read to serve
    ``requests``, (prompt, decoded) pairs: the weights once a step, and the
    cache at every position each request's decode steps attend to."""
    positions = sum(p + j + 1 for p, d in requests for j in range(d - 1))
    return steps * decode_weight_bytes(c) + positions * kv_bytes_per_position(c)
