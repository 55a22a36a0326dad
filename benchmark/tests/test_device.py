import pytest

from benchmark.lib import costs, device


def test_v5e_peaks_are_the_published_ones():
    p = device.peaks("TPU v5 lite")
    assert (p["flops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        197e12, 819e9, 16e9)
    assert p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_process_age_is_positive_and_small():
    assert 0.0 <= device.process_age_s() < 3600


def test_cost_functions_on_hand_counted_shapes():
    # one decode row over a 1000-token context, 16 heads of 128
    f, b = costs.paged_attention_cost(1000, 1, 16, 16, 128)
    assert f == 4 * 1000 * 16 * 128
    assert b == 2 * 1000 * 16 * 128 * 2 + 2 * 16 * 128 * 2
    # a 256-row chunk that ends a 512-token context sees 256*256 + 256*257/2
    f, _ = costs.paged_attention_cost(512, 256, 16, 16, 128)
    assert f == 4 * (256 * 256 + 256 * 257 / 2) * 16 * 128
    f, b = costs.flash_attention_cost(2, 8, 4, 4, 16, backward=False)
    assert f == 2 * 2 * (2 * 4 * 8 * 9 / 2) * 16
    assert b == 4 * (2 * 8 * 4 * 16 * 2)
    fb, bb = costs.flash_attention_cost(2, 8, 4, 4, 16, backward=True)
    assert fb == 2.5 * f and bb == 2 * b
    least, bound = costs.roofline_seconds(197e12, 819e9, device.peaks(
        "TPU v5 lite"))
    assert least == 1.0 and bound == "flops"
    assert costs.transformer_flops_per_token(10, 2, 3, 4) == 60 + 288
