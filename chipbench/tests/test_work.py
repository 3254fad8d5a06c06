"""Required bytes and the peaks table."""

import pytest

from chipbench import peaks, work


def test_bytes_per_site_follow_the_fields_component_counts():
    from repro.apps.ludwig import lc
    from repro.kernels.wilson_dslash import ref as wref
    from repro.maths import d3q19

    assert work.LUDWIG_DIST == d3q19.NVEL
    assert work.LUDWIG_Q == lc.NQCOMP
    assert work.WILSON_SPINOR == wref.SPINOR_NCOMP
    assert work.WILSON_GAUGE == wref.GAUGE_NCOMP
    assert work.ludwig_bytes_per_site() == 192
    assert work.cg_bytes_per_site() == 864
    assert work.ludwig_bytes_per_site(itemsize=2) == 96


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
