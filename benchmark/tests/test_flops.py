"""flops.py against hand-worked counts."""
from benchmark import flops, peaks

import pytest


def test_resnet50_forward_macs_by_hand():
    # stem: 7x7x3 -> 64 at 112x112
    stem = 3 * 64 * 49 * 112 * 112
    # stage 1 at 56x56: first block from 64 channels with a projection
    s1_first = (64 * 64 + 64 * 64 * 9 + 64 * 256 + 64 * 256) * 56 * 56
    s1_rest = (256 * 64 + 64 * 64 * 9 + 64 * 256) * 56 * 56
    # stage 2 at 28x28 (stride on the first 1x1), 3, 4 likewise
    s2_first = (256 * 128 + 128 * 128 * 9 + 128 * 512 + 256 * 512) * 28 * 28
    s2_rest = (512 * 128 + 128 * 128 * 9 + 128 * 512) * 28 * 28
    s3_first = (512 * 256 + 256 * 256 * 9 + 256 * 1024 + 512 * 1024) * 14 * 14
    s3_rest = (1024 * 256 + 256 * 256 * 9 + 256 * 1024) * 14 * 14
    s4_first = (1024 * 512 + 512 * 512 * 9 + 512 * 2048 + 1024 * 2048) * 7 * 7
    s4_rest = (2048 * 512 + 512 * 512 * 9 + 512 * 2048) * 7 * 7
    by_hand = (stem + s1_first + 2 * s1_rest + s2_first + 3 * s2_rest
               + s3_first + 5 * s3_rest + s4_first + 2 * s4_rest
               + 2048 * 1000)
    got = flops.resnet_v1_forward_macs(50, 224, 1000)
    assert got == by_hand
    # He et al., Table 1: 3.8e9 multiply-adds for the 50-layer net
    assert 3.8e9 < got < 3.9e9
    assert flops.train_flops_per_image(got) == 6 * got


def test_decoder_counts_at_opt_6_7b_widths():
    d, f, v = 4096, 16384, 50272
    layer = flops.decoder_layer_weight_count(d, f)
    assert layer == 4 * 4096 * 4096 + 2 * 4096 * 16384 == 201326592
    # 32 layers of matrices + embedding: the published 6.7B
    assert 6.6e9 < 32 * layer + v * d < 6.8e9
    # a decode step with nothing live reads just the weights, in fp32
    assert flops.decode_step_bytes(4, d, f, v, 0) == (4 * layer + d * v) * 4
    # and 1000 live tokens add K and V of 4 layers
    assert flops.decode_step_bytes(4, d, f, v, 1000) \
        - flops.decode_step_bytes(4, d, f, v, 0) == 2 * 4 * 1000 * d * 4


def test_peaks_table_has_the_chip_and_no_default():
    row = peaks.peak("TPU v5 lite")
    assert row["flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9
    assert "cpu" not in peaks.PEAKS
    with pytest.raises(KeyError):
        peaks.peak("cpu")
