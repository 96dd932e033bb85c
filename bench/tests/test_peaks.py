"""Peaks come only from bench/peaks.json; an unknown device is an error."""
import pytest

from harness import peaks


def test_v5e_peaks_and_their_source():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_unknown_device_is_refused(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup(kind)
