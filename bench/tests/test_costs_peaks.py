"""Peaks by device kind, and the work of one kernel call."""
import importlib

import pytest

from bench.lib import peaks


def test_v5e_peaks_and_unknown_device():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no peaks"):
        peaks.peak("TPU v9 imaginary")


def test_least_time_takes_the_binding_bound():
    # 197 GFLOP alone takes 1 ms; 819 MB alone takes 1 ms
    assert peaks.least_time_s(197e9, 1.0, "TPU v5 lite") == \
        pytest.approx(1e-3)
    assert peaks.least_time_s(1.0, 819e6, "TPU v5 lite") == \
        pytest.approx(1e-3)
    assert peaks.least_time_s(197e9, 2 * 819e6, "TPU v5 lite") == \
        pytest.approx(2e-3)


def test_codr_matmul_work_of_a_decode_projection():
    cm = importlib.import_module("bench.costs.codr_matmul")
    flops, nbytes = cm.work(32, 2048, 11008, 4)
    assert flops == 2 * 32 * 2048 * 11008
    assert nbytes == 2048 * 11008 / 2 + (32 * 2048 + 32 * 11008) * 2


def test_conv_work_of_vgg16_conv1_2():
    cv = importlib.import_module("bench.costs.conv")
    flops, nbytes = cv.work(1, 224, 224, 64, 64, 3, 3)
    assert flops == 2 * 222 * 222 * 64 * 64 * 9
    assert nbytes == 4 * (224 * 224 * 64 + 64 * 64 * 9 + 222 * 222 * 64)
    f1, _ = cv.work(1, 226, 226, 3, 64, 3, 3)
    # 3.81 GFLOP per image over both layers
    assert (f1 + flops) / 1e9 == pytest.approx(3.81, abs=0.01)
