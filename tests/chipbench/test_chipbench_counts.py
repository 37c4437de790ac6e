"""Operation and byte counts against hand counts at small shapes, and the
peaks table."""

from __future__ import annotations

import pytest

from chipbench import counts

TINY = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
        "vocab_size": 10}


def test_sketch_counts():
    # Y (3x5) = A (3x4) @ Omega (4x5): 3*5 dot products of length 4
    flops, nbytes = counts.sketch(3, 4, 5)
    assert flops == 2 * 3 * 5 * 4
    assert nbytes == 4 * (3 * 4) + 4 * (3 * 5)      # A read, Y written


def test_kv_bytes_per_position():
    # K and V, 2 layers, 2 kv heads of 2, bf16: 2 x 2 x 2 x 2 x 2 = 32
    assert counts.kv_bytes_per_position(dict(TINY, torch_dtype="bfloat16")
                                        ) == 32
    qwen3 = {"num_hidden_layers": 28, "num_key_value_heads": 8,
             "head_dim": 128, "torch_dtype": "bfloat16"}
    assert counts.kv_bytes_per_position(qwen3) == 114688


def test_model_flops_hand_count():
    # per layer: q 8x4x2=64, k,v 2x8x2x2=64, o 8x8=64, mlp 3x8x16=384;
    # two layers 1152, LM head 8x10=80 -> 1232 multiply-adds
    assert counts.dense_params_per_token(TINY) == 1232
    # attention: q.K and p.V over 3 positions, 4 heads of 2, 2 layers
    assert counts.model_flops(TINY, 3) == 2 * 1232 + 4 * 2 * 4 * 2 * 3


def test_roofline_share_and_peaks():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    # memory-bound: 819 MB at 819 GB/s is 1 ms; taking 2 ms is 50%
    assert counts.roofline_share(1e9, 819e6, 2e-3, peak) == pytest.approx(50)
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v99")
    with pytest.raises(ValueError):
        counts.roofline_share(1, 1, 0.0, peak)
