"""The table of chip peaks: known kinds read, an unknown kind is an error."""
import pytest

from harness.peaks import peaks


def test_v5e_peaks():
    p = peaks("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks("cpu")
