"""The benchmark's FLOP and size arithmetic against counts worked by hand."""
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.flops import dense, lenet  # noqa: E402

QWEN2_05B = {"hidden_size": 896, "num_hidden_layers": 24,
             "num_attention_heads": 14, "num_key_value_heads": 2,
             "intermediate_size": 4864, "vocab_size": 151936}


def test_lenet_forward_flops_by_layer():
    # 3x3 convs: 2*9*cin*cout per output position; pools: 4 per output
    # element; dense: 2*in*out (28x28 input, VALID conv1-4, SAME conv5-6)
    want = [2 * 9 * 1 * 32 * 26 * 26, 2 * 9 * 32 * 32 * 24 * 24,
            12 * 12 * 32 * 4, 2 * 9 * 32 * 64 * 10 * 10,
            2 * 9 * 64 * 64 * 8 * 8, 4 * 4 * 64 * 4,
            2 * 9 * 64 * 128 * 4 * 4, 2 * 9 * 128 * 128 * 4 * 4,
            2 * 2 * 128 * 4, 2 * 512 * 382, 2 * 382 * 192, 2 * 192 * 10]
    assert [f for _, _, f, _ in lenet.layers(28)] == want
    assert sum(want) == 27_055_360
    assert lenet.train_flops_per_sample({"input_hw": 28}) == 3 * 27_055_360


def test_lenet_cut_profile_at_cut_1():
    p = lenet.profile({"input_hw": 28})
    assert p["xi_d"][0] == (9 * 32 + 32) * 32            # CONV1 params, bits
    assert p["xi_s"][0] == 26 * 26 * 32 * 32             # smashed bits
    assert p["gamma_dF"][0] == 389_376
    assert p["gamma_sF"][0] == 27_055_360 - 389_376
    assert np.array_equal(p["gamma_dB"], p["gamma_dF"])  # paper: BP == FP


def test_qwen2_05b_forward_flops_at_512():
    attn_params = 896 * 896 + 2 * 896 * 128 + 896 * 896  # q, k, v, o
    mlp_params = 3 * 896 * 4864
    per_layer = (2 * 512 * (attn_params + mlp_params)
                 + 4 * 512 * 512 * 14 * 64)
    head = 2 * 512 * 896 * 151936
    assert 24 * per_layer + head == 528_364_863_488
    assert dense.forward_flops(QWEN2_05B, 512) == 528_364_863_488
    assert dense.train_flops_per_sample(QWEN2_05B, 512) \
        == 3 * 528_364_863_488


def test_qwen2_05b_cut_profile_at_cut_2():
    p = dense.profile(QWEN2_05B, 512)
    layer_params = 896 * 896 * 2 + 2 * 896 * 128 + 3 * 896 * 4864 + 2 * 896
    assert p["xi_d"][1] == (151936 * 896 + 2 * layer_params) * 32
    assert p["xi_s"][1] == 512 * 896 * 16
    assert p["gamma_dB"][1] == 2 * p["gamma_dF"][1]
    assert p["gamma_dF"][1] + p["gamma_sF"][1] == 528_364_863_488
