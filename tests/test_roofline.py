"""The roofline's per-chip peaks come from one table keyed by jax's
``device_kind``: a known chip reads its published numbers, and a kind
missing from the table is an error, never a silent default."""

import pytest

from repro.analysis import roofline


def test_v5e_peaks_are_the_published_ones():
    peak = roofline.peaks("TPU v5 lite")
    assert peak == {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}
    assert roofline.peaks(roofline.DRYRUN_KIND) is peak


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")
