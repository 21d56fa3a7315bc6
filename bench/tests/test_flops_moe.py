"""Operation and byte counts of the Moonlight cloud pool (``flops_moe``)
against hand counts on a tiny configuration, and the reader of the held
experts' device time on a hand-made trace."""
from types import SimpleNamespace

import flops_moe
from systems import routed_moe_pools as moe
from xplane import Event

TINY = {"hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 4,
        "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 3,
        "intermediate_size": 10, "moe_intermediate_size": 4,
        "n_routed_experts": 2, "router_experts": 8, "num_experts_per_tok": 4,
        "n_shared_experts": 2, "first_k_dense_replace": 1,
        "num_hidden_layers": 3, "vocab_size": 5}


def test_layer_and_request_counts_match_a_hand_count():
    # attention: q 8 x 2*5 = 80, kv_a 8 x (4+2) = 48, kv_b 4 x 2*6 = 48,
    # o 6 x 8 = 48 -> 224 weights
    assert flops_moe.attention_params(TINY) == 224
    # dense layer: + 3 x 8 x 10 = 240 -> 2 * 464 operations a token
    assert flops_moe.layer_matmul_flops(TINY, True) == 928
    # expert layer: router 8 x 8 = 64, shared 2 x 96 = 192, and 4 of 8
    # experts held... 4 chosen x 2/8 held = 1 expert of 96 -> 224 + 352
    assert flops_moe.expert_params(TINY) == 96
    assert flops_moe.layer_matmul_flops(TINY, False) == 2 * (224 + 352)
    assert flops_moe.token_flops(TINY) == 928 + 2 * 1152
    # attention over c positions: 2 heads x (3 + 2 + 3) x c MACs
    assert flops_moe.attention_flops(TINY, 5) == 160
    assert flops_moe.head_flops(TINY) == 80
    # prompt 2, 3 decoded: prefill 2 tokens (attending to 1 and 2) and the
    # head, then 2 decode steps attending to 3 and 4 positions
    tok, att = 3232, 32          # token_flops; attention_flops per position
    want = 2 * tok + 3 * (att * 1 + att * 2) + 80
    want += tok + 3 * att * 3 + 80 + tok + 3 * att * 4 + 80
    assert flops_moe.request_flops(TINY, 2, 3) == want


def test_expert_counts_match_a_hand_count():
    assert flops_moe.expert_flops(TINY, 10) == 2 * 10 * 96
    assert flops_moe.expert_bytes(TINY, 3) == 2 * 3 * 96


HLO = """HloModule jit__decode_slab_impl
  %fusion.1 = f32[4] fusion(%p), metadata={op_name="jit(f)/while/body/moe.experts/dot_general"}
  %fusion.2 = f32[4] fusion(%p), metadata={op_name="jit(f)/while/body/moe.route/top_k"}
  ROOT %custom-call.3 = f32[4] custom-call(%p), metadata={op_name="jit(f)/moe.experts/ragged_dot"}
"""


def test_expert_device_time_reads_scoped_ops_inside_cloud_decode_spans():
    ops = [Event("%fusion.1", 10, 20), Event("%fusion.2", 20, 30),
           Event("%custom-call.3", 30, 45),
           # the same names outside a cloud decode step (another program)
           Event("%fusion.1", 100, 150), Event("%custom-call.3", 200, 260)]
    spans = [Event(moe.DECODE_SPAN, 5, 50), Event(moe.DECODE_SPAN, 300, 400),
             Event("bench.step", 0, 500)]
    rec = SimpleNamespace(trace=SimpleNamespace(ops=[ops], spans=spans),
                          extra={"cloud_decode_hlo": HLO})
    assert abs(moe.expert_device_s(rec) - 25e-9) < 1e-18
    assert moe.expert_device_s(SimpleNamespace(
        trace=rec.trace, extra={})) is None
