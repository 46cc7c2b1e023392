"""The yardstick's counts and peaks."""

import pytest

from bench import counts
from bench.references import stencil


@pytest.mark.parametrize("ndim,expected", [(2, 33), (3, 49)])
def test_flops_per_cell_is_table_one(ndim, expected):
    # paper Table I for a star of radius 4: 8r+1 in 2D, 12r+1 in 3D
    taps = stencil.offsets("star", ndim, 4)
    assert counts.flops_per_cell(len(taps)) == expected


def test_bytes_are_one_read_and_one_write_of_the_grid():
    cfg = {"grid": [16384, 16384], "program": {"dtype": "float32"}}
    assert counts.cells(cfg) == 16384 ** 2
    assert counts.bytes_per_call(cfg) == 2 * 4 * 16384 ** 2


def test_peaks_are_keyed_by_device_kind_with_sources():
    p = counts.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["peak_bf16_flops"] == 197e12
    assert "TPU v5e" in p["source"]
    assert p["peak_vpu_f32_flops_source"].startswith("derived")
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_roofline_names_the_larger_bound():
    p = counts.peaks("TPU v5 lite")
    t, side = counts.roofline_seconds(p["peak_vpu_f32_flops"], 0.0, p, 1)
    assert (t, side) == (1.0, "vpu")
    t, side = counts.roofline_seconds(0.0, 2 * p["hbm_bytes_per_s"], p, 4)
    assert (t, side) == (0.5, "hbm")
