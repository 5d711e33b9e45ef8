import benchpath  # noqa: F401
import pytest

from harness import peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks(kind)
