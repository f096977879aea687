"""The peaks table: the v5e row as published, and no default for a device
it does not know."""

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the import path)

import pytest

from benchlib.peaks import UnknownDevice, peaks_for


def test_v5e_row():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(UnknownDevice):
        peaks_for(kind)
