"""The FLOP and byte functions against hand counts."""
import json
import os

import pytest

import harness

CFG = harness.load_json(os.path.join(harness.BENCH, "configs",
                                     "flsimco-r18-single.json"))


def test_forward_macs_match_hand_count():
    flops = harness.flops_module(CFG)
    full = 3 * 3 * 64 * 64 * 32 * 32          # a 3x3 64->64 conv at 32x32
    stem = 3 * 3 * 3 * 64 * 32 * 32
    stage1 = 4 * full
    # stages 2-4: a strided 3x3 conv (half the work), three full ones and
    # a 1x1 strided shortcut, each stage doubling channels, halving H, W
    later = 3 * (full // 2 + 3 * full + full // 18)
    projector = 512 * 512 + 512 * 128
    assert stem + stage1 + later + projector == 555_745_280   # ~555.7M
    assert flops.forward_macs_per_image(CFG) == 555_745_280


def test_train_flops_are_three_passes_less_the_stem_input_gradient():
    flops = harness.flops_module(CFG)
    fwd = 2 * flops.forward_macs_per_image(CFG)
    stem = 32 * 32 * 3 * 3 * 3 * 64
    assert flops.train_flops_per_view(CFG) == 3 * fwd - 2 * stem
    traffic = {"vehicles_per_round": 5}
    per_round = flops.round_flops(CFG, traffic)
    assert per_round == 5 * 256 * 2 * (3 * fwd - 2 * stem)
    assert 8.4e12 < per_round < 8.6e12


def test_model_values_match_the_published_count():
    flops = harness.flops_module(CFG)
    assert flops.n_values(CFG) == 11_506_624


@pytest.mark.parametrize("peak_flops,peak_bytes", [(197e12, 819e9),
                                                   (1e18, 819e9),
                                                   (197e12, 1e18)])
def test_conv_roofline_is_the_larger_bound_per_convolution(peak_flops,
                                                           peak_bytes):
    flops = harness.flops_module(CFG)
    total, by_f, by_b = flops.conv_roofline_s(
        CFG, {"vehicles_per_round": 5}, peak_flops, peak_bytes)
    assert total == pytest.approx(by_f + by_b)
    if peak_flops > 1e17:
        assert by_f == 0.0
    if peak_bytes > 1e17:
        assert by_b == 0.0
    conv_flops = sum(
        (3 if i else 2) * 2 * 5 * 256 * 2 * ho * wo * kh * kw * ci * co
        for i, (_, _, _, ho, wo, kh, kw, ci, co)
        in enumerate(flops.convs(CFG)))
    assert total >= conv_flops / peak_flops * (1 - 1e-12)


def test_peaks_name_their_source_and_refuse_an_unknown_kind():
    table = json.load(open(os.path.join(harness.BENCH, "peaks.json")))
    assert "TPU v5e" in table["source"]
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit, match="no peaks for device kind"):
        harness.peaks_for("TPU v9 imaginary")
