"""Work counts and the peaks table, against hand-computed values."""

import pytest

from bench import costs


def test_integral_image_counts_two_adds_and_one_read_and_write_per_pixel():
    assert costs.integral_image(2, 3, 4) == (48.0, 192.0)


def test_quant_matmul_counts_int8_operands_and_float32_result():
    # 2*2*3*4 ops; 2*3 + 3*4 int8 bytes in, 2*4*4 out, 4*4 of bias
    assert costs.quant_matmul(2, 3, 4) == (48.0, 66.0)


def test_bilateral_blur_counts_both_grids_on_three_axes():
    # 24 vertices x 2 grids x 3 axes x 4 ops; 2 grids read + written, f32
    assert costs.bilateral_blur(2, 3, 4) == (576.0, 384.0)


def test_bssa_pair_sums_its_four_steps():
    assert costs.grid_dims(32, 48, 16, 16.0) == (3, 4, 17)
    px = 32 * 48
    want = px * 3 * 8 + px * 8 + 1 * 24 * (3 * 4 * 17) + px * 52
    assert costs.bssa_pair(32, 48, 2, 1, 16, 16.0) == want == 133920.0


def test_funnel_frame_counts_motion_frames_only_past_the_gate():
    still = costs.funnel_frame(16, 16, False, 10, 2, 0)
    assert still == 256 + 3 * 2 * 2
    moving = costs.funnel_frame(16, 16, True, 10, 2, 1)
    assert moving == still + 5 * 256 + 140 + 360 + 2 * (400 * 8 + 8)


@pytest.mark.parametrize("ops,nbytes,bound", [(1e12, 1e8, "compute"),
                                              (1e9, 1e9, "memory")])
def test_roofline_names_its_bound(ops, nbytes, bound):
    share, got = costs.roofline(ops, nbytes, 0.02, 1e14, 1e11)
    assert got == bound
    assert share == pytest.approx(100 * max(ops / 1e14, nbytes / 1e11) / 0.02)


def test_roofline_of_nothing_is_none():
    assert costs.roofline(0, 0, 1.0, 1e14, 1e11) is None
    assert costs.roofline(1e9, 1e9, 0.0, 1e14, 1e11) is None


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")
