"""``bench/flops.py`` against hand counts, and the table of peaks."""
import json
import os

import pytest

from bench import flops

HERE = os.path.dirname(os.path.abspath(__file__))
GRANITE = json.load(open(os.path.join(HERE, "..", "..", "bench", "configs",
                                      "granite-8b-2l.json")))
QWEN_8L = {"num_hidden_layers": 8, "hidden_size": 5120,
           "num_attention_heads": 40, "num_key_value_heads": 8,
           "head_dim": 128, "intermediate_size": 13824, "vocab_size": 152064}


def test_granite_by_hand():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, MLP 3 x 4096x14336
    layer = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 58_720_256
    assert flops.layer_matmul_params(GRANITE) == layer == 218_103_808
    weights = 2 * layer + 4096 * 49152            # 2 layers and the head
    attn = 4 * 2 * 4096 * (1024 + 1) / 2          # QK and PV, causal mean
    fwd = 2 * weights + attn
    assert flops.forward_flops_per_token(GRANITE, 1024) == fwd
    assert flops.train_flops_per_token(GRANITE, 1024) == 3 * fwd
    assert 3 * fwd * 4096 == pytest.approx(15.874e12, rel=1e-4)


def test_qwen_by_hand():
    layer = 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 5120 + 3 * 5120 * 13824
    assert flops.layer_matmul_params(QWEN_8L) == layer == 275_251_200
    weights = 8 * layer + 5120 * 152064
    fwd = 2 * weights + 4 * 8 * 5120 * (2048 + 1) / 2
    assert flops.train_flops_per_token(QWEN_8L, 2048) == 3 * fwd


def test_serving_by_hand():
    layer = flops.layer_matmul_params(GRANITE)
    # a 3-token prompt: positions attend to 1, 2 and 3 keys; head once
    assert flops.prefill_flops(GRANITE, 3) == (
        2 * 2 * layer * 3 + 4 * 2 * 4096 * 6 + 2 * 4096 * 49152)
    # a token decoded over 10 cached positions attends to 11
    assert flops.decode_flops(GRANITE, 10) == (
        2 * (2 * layer + 4096 * 49152) + 4 * 2 * 4096 * 11)


def test_peaks_are_keyed_by_device_kind():
    assert flops.peak("TPU v5 lite") == 197e12
    assert flops.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        flops.peak("cpu")
