"""chipbench/flops.py against hand counts."""
import json
import os

import pytest

from chipbench import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def mistral():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mistral7b-v03-d2.json")) as f:
        return json.load(f)


def test_one_bottleneck_block_by_hand():
    # conv3_1 of the paper's table: 56x56x256 in, mid 128, stride 2.
    by_hand = 2 * (56 * 56 * 256 * 128          # 1x1 at the input's size
                   + 28 * 28 * 9 * 128 * 128    # 3x3 carries the stride
                   + 28 * 28 * 128 * 512        # 1x1 up
                   + 28 * 28 * 256 * 512)       # projection shortcut
    assert flops.bottleneck_forward_flops(56, 256, 128, 2, True) == by_hand


def test_resnet50_forward_is_the_published_four_gigaflops():
    # He et al. give 3.8e9 multiply-adds for the v1 net; stride on the 3x3
    # (v1.5) adds ~0.3e9. Train = 3x forward less the stem's input gradient.
    train = flops.resnet50_train_flops(224, 1000)
    stem = flops.conv_flops(112, 7, 3, 64)
    forward_macs = (train + stem) / 3 / 2
    assert 4.0e9 < forward_macs < 4.2e9


def test_one_decoder_layer_by_hand():
    sizes = mistral()
    by_hand = (4096 * 4096 * 2          # q, o
               + 4096 * 1024 * 2        # k, v at 8 heads of 128
               + 4096 * 14336 * 3)      # gate, up, down
    assert flops.decoder_layer_params(sizes) == by_hand == 218_103_808
    step = flops.decoder_train_flops(sizes, 4, 4096)
    matmul = 6 * (2 * by_hand + 4096 * 32768) * 4 * 4096
    attention = 3 * 2 * (2 * 4 * 32 * 4096 * 4096 * 128)
    assert step == matmul + attention
    assert 5.9e13 < step < 6.0e13


def test_one_causal_flash_call_by_hand():
    call = flops.flash_call("fwd", 4, 32, 8, 4096, 128)
    # Two products over the lower triangle: 2 * (2 * s * s * d) / 2 a head.
    assert call["flops"] == 4 * 32 * 2 * 4096 * 4096 * 128
    q = 4 * 4096 * 32 * 128 * 2
    kv = 4 * 4096 * 8 * 128 * 2
    assert call["bytes"] == 2 * q + 2 * kv + 4 * 32 * 4096 * 4
    bwd = flops.flash_call("bwd", 4, 32, 8, 4096, 128)
    assert bwd["flops"] == call["flops"] * 5 // 2
    seconds, bound = flops.least_seconds(call, flops.peaks("TPU v5 lite"))
    assert bound == "compute" and seconds == call["flops"] / 197e12
    with pytest.raises(ValueError):
        flops.flash_call("sideways", 1, 1, 1, 8, 8)


def test_an_unknown_chip_is_an_error_not_a_default():
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
