"""Roofline peaks are keyed by ``device_kind``; an unknown device is an error."""
import jax
import pytest

from repro.launch import roofline


def test_v5e_peaks_are_the_published_figures():
    peaks = roofline.device_peaks("TPU v5 lite")
    assert peaks == {"peak_flops": 197e12, "mem_bw": 819e9, "link_bw": 50e9}


def test_default_is_the_live_device_kind():
    assert roofline.device_peaks() is roofline.DEVICE_PEAKS[jax.devices()[0].device_kind]


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "NVIDIA H100", "tpu"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="no peaks recorded"):
        roofline.device_peaks(kind)
