"""The FLOP and byte functions against counts made by hand."""
import pytest

import costs
from dims import Dims


def small(mlp="gated", window=None):
    return Dims(name="t", n_layers=2, d_model=8, n_heads=2, n_kv_heads=1,
                head_dim=4, d_ff=16, vocab=32, mlp=mlp, norm="rms",
                norm_eps=1e-6, rope_theta=1e4, window=window)


def test_linear_shapes():
    d = small()
    assert d.linear_shapes == [("q", 8, 8), ("k", 8, 4), ("v", 8, 4),
                               ("o", 8, 8), ("gate", 8, 16), ("up", 8, 16),
                               ("down", 16, 8)]
    assert [s[0] for s in small("plain").linear_shapes] == \
        ["q", "k", "v", "o", "up", "down"]


def test_generate_flops_by_hand():
    # per token per layer: weight matmuls 2 * (64+32+32+64+128+128+128) = 1152
    # lm_head 2 * 8 * 32 = 512 per sampled position
    # attention 4 * H * hd * span = 32 * span per layer
    # batch 1, prompt 3, new 2: prefill 3 tokens (spans 1, 2, 3), one
    # lm_head; then one decode step at position 3 (span 4) with its lm_head
    lin = 1152 * 2
    prefill = 3 * lin + 512 + 32 * 2 * (1 + 2 + 3)
    decode = lin + 512 + 32 * 2 * 4
    assert costs.generate_flops(small(), 1, 3, 2) == prefill + decode
    assert costs.generate_flops(small(), 5, 3, 2) == 5 * (prefill + decode)


def test_window_caps_attention_span():
    full = costs.generate_flops(small(), 1, 3, 2)
    capped = costs.generate_flops(small(window=2), 1, 3, 2)
    # spans 1, 2, 2 in prefill and 2 in decode instead of 1, 2, 3 and 4
    assert full - capped == 32 * 2 * (1 + 2)


def test_aged_matmul_cost_and_floor():
    assert costs.aged_matmul_cost(2, 3, 4) == (48.0, 6 + 12 + 24 + 32)
    d = small()
    # one op/s and one byte/s: every matmul is bound by its bytes here
    per_layer = sum(max(*costs.aged_matmul_cost(1, k, n))
                    for _, k, n in d.linear_shapes)
    assert costs.aged_matmul_floor_s(d, 1, 1, 3, 1.0, 1.0) == \
        pytest.approx(3 * 2 * per_layer)
