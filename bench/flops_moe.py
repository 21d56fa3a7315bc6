"""Model operations and bytes of serving a Moonlight (DeepSeek-V3) model,
counted from its configuration group's shapes; ``flops.py`` counts the
dense decoders and holds the chip's peaks.

A multiply-add is 2 operations.  Per token through one layer:

- latent attention's projections: q (d x H(qk_nope + qk_rope)), kv_a
  (d x (kv_lora_rank + qk_rope)), kv_b (kv_lora_rank x H(qk_nope + v)) and
  the output (H v x d); and its scores and weighted values over the
  positions the token attends to, per head qk_nope + qk_rope and v wide
  (the expanded form's count: the absorbed decode does the same work
  through the latent);
- the first ``first_k_dense_replace`` layers: a dense SwiGLU of
  ``intermediate_size``;
- the others: the router (d x router_experts), the shared experts (a
  SwiGLU of n_shared x moe_intermediate_size) and, at this chip's held
  share, ``num_experts_per_tok x n_routed_experts / router_experts``
  routed experts (each a SwiGLU of moe_intermediate_size): the experts a
  token reaches here on average under uniform routing.

The output head runs once per generated token.  Norms, rotary embedding,
softmax and the routing's sort are not counted.

Held experts' work in the decode steps is counted from the program's own
counters instead (``expert_flops``, ``expert_bytes``): the rows they
computed, and the bytes at bfloat16 of the experts that got any.
"""
from __future__ import annotations

#: bytes of a published bfloat16 weight
BF16 = 2


def expert_params(c: dict) -> int:
    """One routed expert's weights (gate, up, down)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def attention_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    r, dn, dr, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def layer_matmul_flops(c: dict, dense: bool) -> float:
    """Per token per layer: attention's projections and the MLP or the
    expert layer at this chip's share."""
    d = c["hidden_size"]
    if dense:
        mlp = 3 * d * c["intermediate_size"]
    else:
        routed = c["num_experts_per_tok"] * c["n_routed_experts"] \
            / c["router_experts"]
        mlp = d * c["router_experts"] \
            + c["n_shared_experts"] * expert_params(c) \
            + routed * expert_params(c)
    return 2.0 * (attention_params(c) + mlp)


def token_flops(c: dict) -> float:
    """Per token through every layer, projections and MLPs."""
    k = c["first_k_dense_replace"]
    return k * layer_matmul_flops(c, True) \
        + (c["num_hidden_layers"] - k) * layer_matmul_flops(c, False)


def attention_flops(c: dict, context: int) -> float:
    """Per token per layer attending to ``context`` positions."""
    per_head = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return 2.0 * c["num_attention_heads"] * per_head * context


def head_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def request_flops(c: dict, prompt: int, decoded: int) -> float:
    """One served request: the prompt's prefill (token i attends to i + 1
    positions) with the head at its last position, then ``decoded - 1``
    decode steps, step j attending to ``prompt + j + 1`` positions."""
    n = c["num_hidden_layers"]
    total = prompt * token_flops(c) + head_flops(c)
    total += n * sum(attention_flops(c, i + 1) for i in range(prompt))
    for j in range(decoded - 1):
        total += token_flops(c) + n * attention_flops(c, prompt + j + 1) \
            + head_flops(c)
    return total


def expert_flops(c: dict, rows: int) -> float:
    """Operations of ``rows`` (token, expert) rows through held experts."""
    return 2.0 * rows * expert_params(c)


def expert_bytes(c: dict, hit: int) -> int:
    """Weights at bfloat16 that ``hit`` expert uses (an expert in one layer
    in one step that got any row) must read."""
    return BF16 * hit * expert_params(c)
