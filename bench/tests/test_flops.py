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


def test_decode_bytes_match_a_hand_count():
    # weights per step: 3 layers x 576 + head 8 x 10 = 1808, at 2 bytes
    assert flops.layer_params(TINY) == 576
    assert flops.decode_weight_bytes(TINY) == 3616
    # keys and values of one position: 2 x 3 layers x 1 head x 4 dims x 2 B
    assert flops.kv_bytes_per_position(TINY) == 48
    # prompt of 2, 3 tokens decoded: 2 steps attending to 3 and 4 positions;
    # prompt of 5, 1 token: no decode step
    assert flops.decode_bytes(TINY, 2, [(2, 3), (5, 1)]) == 2 * 3616 + 7 * 48
    assert flops.decode_flops(TINY, 2, 3) == \
        3 * (1152 + 96) + 160 + 3 * (1152 + 128) + 160
    # a tied head reads the token embedding: the same count
    assert flops.decode_weight_bytes(dict(TINY, tie_word_embeddings=True)) \
        == 3616


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def time_s(self, pattern, modules=False):
        assert modules and pattern == "_decode_slab_impl"
        return self.seconds


def test_decode_roofline_reader_counts_bytes_per_pool():
    from harness import reader
    from record import Record

    read = reader("decode_slab_roofline")
    cfg = dict(TINY, tie_word_embeddings=False)
    segs = [{"tier": 0, "prompt": 2, "decoded": 3},
            {"tier": 1, "prompt": 5, "decoded": 2}]
    rec = Record(segments=segs, device_kind="TPU v5 lite",
                 extra={"decode_steps": {0: 2, 1: 1},
                        "configs": {0: cfg, 1: cfg}})
    assert read(rec) is None                       # no trace
    rec.trace = _Trace(1e-6)
    # pool 0: 2 steps, 7 positions; pool 1: 1 step, 6 positions; both bound
    # by their bytes at these sizes
    want = (3 * 3616 + 13 * 48) / 819e9
    assert read(rec) == pytest.approx(100.0 * want / 1e-6)
    rec.extra["decode_steps"] = {0: 0, 1: 0}
    assert read(rec) is None                       # nothing decoded
