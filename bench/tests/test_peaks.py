import pytest

from harness.peaks import peaks


def test_v5e_peaks():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("cpu")
