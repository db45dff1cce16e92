import pytest

from bench import peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
