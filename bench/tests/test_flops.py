import pytest

import flops

TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 3,
        "vocab_size": 10}


def test_request_flops_match_a_hand_count():
    # per layer per token: q 8x8, k 8x4, v 8x4, o 8x8 = 192 MACs; MLP
    # 3 x 8x16 = 384 MACs -> 2 * 576 = 1152 operations
    assert flops.layer_matmul_flops(TINY) == 1152
    # attention over c positions: (scores + values) 2 heads x 4 dims x c
    # MACs each -> 2 * 2 * 8 * c
    assert flops.attention_flops(TINY, 5) == 160
    assert flops.head_flops(TINY) == 160
    # prompt of 2, 3 tokens decoded: prefill 2 tokens (attending to 1, 2),
    # head once; 2 decode steps attending to 3 and 4 positions
    layers = 3
    want = 2 * layers * 1152 + layers * (32 + 64) + 160
    want += layers * (1152 + 96) + 160 + layers * (1152 + 128) + 160
    assert flops.request_flops(TINY, 2, 3) == want


def test_peaks_are_keyed_by_device_kind():
    assert flops.peak("TPU v5 lite") == 197e12
    assert flops.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        flops.peak("TPU v99")
    with pytest.raises(KeyError):
        flops.peak("cpu")
