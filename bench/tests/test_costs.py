"""The FLOP and byte functions against counts made by hand."""
import pytest

import costs
from families.decoder import Dims


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


def odd():
    """k and v have 2 output columns each, which do not divide over 4."""
    return Dims(name="t", n_layers=2, d_model=8, n_heads=2, n_kv_heads=1,
                head_dim=2, d_ff=12, vocab=32, mlp="gated", norm="rms",
                norm_eps=1e-6, rope_theta=1e4, window=None)


def test_floor_per_chip_by_hand():
    # one op/s and one byte/s; batch 1, prompt 2, new 2: M = 2 once, then
    # M = 1 once, per layer, two layers.  max(2mkn, mk + kn + 4(m+n) + 4mn)
    # at chips 1, (K, N) q (8,4) k (8,2) v (8,2) o (4,8) gate/up (8,12)
    # down (12,8): M=2 128 64 64 144 384 384 384, M=1 76 44 44 104 204
    # 204 192; at chips 4 each N / 4, k and v left out: M=2 q 44 o 48
    # gate/up/down 96, M=1 28 32 60 60 56
    one = (128 + 64 + 64 + 144 + 3 * 384) + (76 + 2 * 44 + 104 + 2 * 204
                                              + 192)
    four = (44 + 48 + 3 * 96) + (28 + 32 + 60 + 60 + 56)
    d = odd()
    assert costs.aged_matmul_floor_s(d, 1, 2, 2, 1.0, 1.0) == 2 * one
    assert costs.aged_matmul_floor_s(d, 1, 2, 2, 1.0, 1.0, chips=1) == \
        2 * one
    assert costs.aged_matmul_floor_s(d, 1, 2, 2, 1.0, 1.0, chips=4) == \
        2 * four


@pytest.mark.parametrize("chips", [1, 4])
def test_readers_per_chip(chips):
    """mfu and aged_matmul_roofline on a synthetic trace of the first
    chip: two mesh programs, 300 ns of kernel time, a 1000 ns window."""
    import cells
    import harness
    import tracefile
    from tracefile import Event
    tr = tracefile.make_trace(
        [Event("fused_aged_matmul.1", 100, 300), Event("fusion.3", 300, 400),
         Event("fused_aged_matmul.2", 500, 600)],
        [Event("jit_sharded_gen(1)", 100, 400),
         Event("jit_sharded_gen(1)", 500, 800)],
        [Event("window", 0, 1000)], (0, 1000))
    traffic = {"batch": 1, "prompt_tokens": 2, "new_tokens": 2,
               "device": {"route": "fused_kernel"}}
    peak = {"int8_ops_per_s": 1e10, "bf16_flops_per_s": 5e9,
            "hbm_bytes_per_s": 1e10}
    ctx = harness.Context(tr, odd(), traffic, peak, chips)
    floor_s = {1: 2 * 2420e-10, 4: 2 * 616e-10}[chips]   # by hand, above
    assert cells.metric_reader("aged_matmul_roofline")(ctx) == \
        pytest.approx(100 * 2 * floor_s / 300e-9)
    flops = 2 * costs.generate_flops(odd(), 1, 2, 2)
    assert cells.metric_reader("mfu")(ctx) == pytest.approx(
        100 * flops / (1000e-9 * chips * 1e10))
    assert harness.Context(tr, odd(), traffic, peak).chips == 1
