"""analysis.roofline: the measured-kernel peak model is keyed by the TPU's
device kind, and a kind without published peaks is an error, never another
chip's numbers."""
import pytest

from repro.analysis import roofline


def test_tpu_peaks_come_from_the_device_kind_table():
    pk = roofline.kernel_peaks("tpu", "TPU v5 lite")
    assert pk["peak_flops_s"] == 197e12 and pk["peak_bytes_s"] == 819e9
    assert not pk["calibrated"]
    rl = roofline.kernel_roofline(
        "oga_step", 4096, 10, 100.0, platform="tpu", device_kind="TPU v5 lite"
    )
    assert rl["peak_bytes_s"] == 819e9 and not rl["peaks_calibrated"]


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", None])
def test_unknown_tpu_kind_raises(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.kernel_peaks("tpu", kind)
