"""FLOP counts against hand counts at small shapes, and the peak table."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from yardstick import flops as F  # noqa: E402
from yardstick import peaks as P  # noqa: E402

# d 8, 2 heads of 4, 1 KV head, d_ff 16, 2 layers, vocab 32
TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "intermediate_size": 16, "num_hidden_layers": 2, "vocab_size": 32}


def test_mlp_train_flops_by_hand():
    # (32*16 + 16) + (16*4 + 4) = 596 weights and biases, 6 per sample
    assert F.mlp_params([32, 16, 4]) == 596
    assert F.mlp_train_flops([32, 16, 4], 8) == 6 * 596 * 8


def test_logistic_regression_flops_by_hand():
    # FedProx's 60 -> 10 multinomial logistic regression: 600 + 10
    assert F.mlp_params([60, 10]) == 610
    assert F.mlp_train_flops([60, 10], 10) == 6 * 610 * 10


def test_matmul_params_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8; gate, up 8x16 and down 16x8; tied 32x8
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert F.matmul_params(TINY) == 2 * per_layer + 32 * 8


def test_attention_flops_by_hand():
    # seq 4: query t reads t + 1 keys, 10 pairs; QK^T and PV, 2 flops per
    # multiply-add over hd = 4: 160 a head a layer
    assert F.attention_flops(TINY, 1, 4) == 2 * 2 * 160
    assert F.attention_flops(TINY, 3, 4) == 3 * F.attention_flops(TINY, 1, 4)


def test_train_flops_is_six_n_d_plus_three_attention():
    n = F.matmul_params(TINY)
    assert F.train_flops(TINY, 2, 4) == 6 * n * 8 + 3 * F.attention_flops(
        TINY, 2, 4)


def test_smollm_counts():
    smollm = {"hidden_size": 576, "num_attention_heads": 9,
              "num_key_value_heads": 3, "intermediate_size": 1536,
              "num_hidden_layers": 30, "vocab_size": 49152}
    # 30 x (576*64*24 + 3*576*1536) + 49152*576
    assert F.matmul_params(smollm) == 134_479_872
    # a round of 16 clients x 2 x 256 tokens: 6 N D = 6.61 TFLOP, and
    # 3 x 30 x 2 x 9 x 2 x 2 x 64 x (256 x 257 / 2) = 13.6 GFLOP of
    # attention a client
    assert 16 * F.train_flops(smollm, 2, 256) == pytest.approx(
        6 * 134_479_872 * 8192 + 16 * 3 * 30 * 2 * 9 * 4 * 64 * 32896)


def test_peaks_v5e_and_unknown_kind_raises():
    v5e = P.peaks("TPU v5 lite")
    assert v5e.flops == 197e12 and v5e.hbm_bw == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        P.peaks("TPU v99")
