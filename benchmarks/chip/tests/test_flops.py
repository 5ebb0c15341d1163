"""FLOP and byte counts against shapes counted by hand."""
import flops


def test_causal_pairs_by_hand():
    assert flops.causal_pairs(4) == 1 + 2 + 3 + 4
    assert flops.causal_pairs(5, window=2) == 1 + 2 + 2 + 2 + 2
    assert flops.causal_pairs(3, window=8) == 6


def test_flash_fwd_counts_true_head_size():
    # B 1, S 4, 2 q heads over 1 kv head, d 80: 10 pairs per head
    f, b = flops.flash_fwd(1, 4, 2, 1, 80)
    assert f == 4 * 80 * 2 * 10
    assert b == 2 * 4 * 80 * (2 + 2 + 1 + 1)


def test_decode_attn_reads_attended_positions_only():
    f, b = flops.decode_attn(batch=2, attended=100, heads=8, kv_heads=2, d=64)
    assert f == 4 * 64 * 8 * 2 * 100
    assert b == 2 * 2 * (2 * 2 * 100 * 64 + 2 * 8 * 64)


def test_danube_forward_matmul_params():
    # hidden 2560: q/o 2560x2560 each, k/v 2560x640 each, MLP 3x2560x6912
    assert flops.layer_matmul_params(2560, 32, 8, 80, 6912) == (
        2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912)


def test_forward_flops_decode_step():
    c = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10}
    # one token after 3 cached: attends 4 positions
    per_layer = 2 * (8 * 4 * (2 * 2 + 2 * 1) + 3 * 8 * 16) + 4 * 4 * 2 * 4
    assert flops.forward_flops(c, 1, 1, past=3) == 2 * per_layer + 2 * 8 * 10


def test_decode_step_bytes():
    c = {"num_hidden_layers": 1, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10}
    w = 2 * (8 * 4 * (2 * 2 + 2 * 1) + 3 * 8 * 16 + 10 * 8)
    assert flops.weight_bytes(c) == w
    assert flops.decode_step_bytes(c, 3, 5) == w + 2 * 3 * 1 * 2 * 1 * 4 * 5
