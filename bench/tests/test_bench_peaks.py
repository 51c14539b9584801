"""The table of chip peaks: keyed by JAX's ``device_kind``, with its
source, and an error for a kind it does not hold."""
import pytest

from benchkit import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", "", "tpu"])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks(kind)
