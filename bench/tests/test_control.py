"""The lower-precision control comes out as not correct, where the program
as it is comes out correct, at a size a test run can hold: the same set-up,
chain and check as a benchmark run, on the CPU past the look for a chip."""

import pytest

from bench import control, spec


@pytest.mark.parametrize("cell,grid", [
    ("star2d_r4_paper.long", [64, 256]),
    ("star2d_r4_paper.step1", [64, 256]),
    ("star3d_r4_paper.long", [16, 32, 128]),
])
def test_control_fails_where_the_program_passes(cell, grid):
    c = spec.resolve(cell)
    c.config = dict(c.config, grid=grid)
    sound = control.readings(c, 21, 3, False, require_tpu=False)
    assert sound["correct"] is True and sound["calls"] == 3
    ctrl = control.readings(c, 21, 3, True, require_tpu=False)
    assert ctrl["correct"] is False
    # every number reads far above the program's own
    for name, v in ctrl["checks"].items():
        assert v["value"] > 100 * max(sound["checks"][name]["value"], 1e-9)

