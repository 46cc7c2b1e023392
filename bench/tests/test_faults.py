"""The check catches a broken timed path: a call that returns its state
unchanged, an answer altered where it is produced, and the exchange
between chips left out.  Each run is a subprocess on the CPU at a small
grid, past the harness's look for a chip."""

import pytest

from bench.tests.runs import cache, check_lines, run_cpu  # noqa: F401


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_broken_timed_path_is_not_correct(cache, fault):
    result, err = run_cpu(cache, "star2d_r4_paper.long", "64x256", 3,
                          0.5, 0, fault)
    assert result["correct"] is False
    assert result["failed"] >= 1
    assert any(float(ln.split()[3]) > float(ln.split()[5])
               for ln in check_lines(err))


def test_exchange_left_out_is_not_correct(cache):
    ok, _ = run_cpu(cache, "star2d_r4_paper.long", "64x256", 4, 0.5, 0,
                    "none", 4)
    assert ok["correct"] is True and ok["device"]["count"] == 4
    bad, _ = run_cpu(cache, "star2d_r4_paper.long", "64x256", 4, 0.5, 0,
                     "no_exchange", 4)
    assert bad["correct"] is False


