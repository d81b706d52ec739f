import pytest

from bench.peaks import UnknownDevice, peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12


def test_unknown_device_is_an_error():
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
