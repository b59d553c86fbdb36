"""The peaks table: keyed by device kind, an unknown kind is an error."""
import pytest

import run


def test_v5e_peaks():
    p = run.peaks_for("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_kind_refused(kind):
    with pytest.raises(KeyError):
        run.peaks_for(kind)
