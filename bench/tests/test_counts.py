"""Operation and byte counts against counts made by hand at small
shapes, and the table of peaks."""
import pytest

from harness import counts, device


def test_decide_kernel_counts_unpadded_rows():
    # 3 jobs x 2 sites: 6 pairs of 31 operations; bytes: 6 transfer
    # times, 5 columns of 3 jobs, 3 columns of 2 sites, 3 outputs
    ops, nbytes = counts.decide_kernel(3, 2)
    assert ops == 31 * 6
    assert nbytes == 4 * (6 + 15 + 6 + 3)


def test_causal_attention_forward_by_hand():
    # b=1, h=1, s=2, hd=1: query 0 sees 1 key, query 1 sees 2; each
    # pair is one multiply-add in q k^T and one in p v
    ops, nbytes = counts.causal_attention_fwd(1, 1, 2, 1)
    assert ops == 2 * 2 * 3
    assert nbytes == 4 * 2 * 4  # q, k, v, o of 2 floats each


def test_lm_train_flops_per_token_by_hand():
    cfg = {"num_hidden_layers": 1, "hidden_size": 2,
           "num_attention_heads": 1, "num_key_value_heads": 1,
           "intermediate_size": 4, "vocab_size": 3}
    # forward: projections 4*2*2=16 params, MLP 3*2*4=24 params -> 80
    # flops; attention at seq 3: 2 keys on average, 2 products of
    # 2*2 flops per key -> 16; head 2*2*3 = 12; total 108, times 3
    assert counts.lm_train_flops_per_token(cfg, 3) == 3 * (80 + 16 + 12)


def test_smollm2_135m_per_token():
    # d 576, 9 heads and 3 kv heads of 64, d_ff 1536, 30 layers, V 49152
    # at seq 2048: projections 2*576*576 + 2*576*192 = 884,736, MLP
    # 3*576*1536 = 2,654,208; per layer 2*(884,736 + 2,654,208) +
    # 4*576*2049/2 = 9,438,336; 30 layers 283,150,080; head 2*576*49152 =
    # 56,623,104; forward 339,773,184; times 3
    cfg = {"num_hidden_layers": 30, "hidden_size": 576,
           "num_attention_heads": 9, "num_key_value_heads": 3,
           "intermediate_size": 1536, "vocab_size": 49152}
    assert counts.lm_train_flops_per_token(cfg, 2048) == 1_019_319_552


def test_roofline_picks_the_binding_bound():
    peak = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_seconds(1000, 10, peak) == (10.0, "compute")
    assert counts.roofline_seconds(10, 1000, peak) == (100.0, "memory")


def test_unknown_device_is_an_error():
    assert device.peak_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        device.peak_for("cpu")
