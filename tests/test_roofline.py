"""Per-chip peaks for the roofline terms are looked up by ``device_kind``."""

import pytest

from repro.utils import roofline as rl


def test_v5e_peaks_are_the_published_ones():
    chip = rl.peaks("TPU v5 lite")
    assert (chip.flops, chip.hbm_bw, chip.link_bw) == (197e12, 819e9, 50e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        rl.peaks(kind)
