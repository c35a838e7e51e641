import pytest

import peaks


def test_v5e_published_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_s"] == 819e9


def test_unknown_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
