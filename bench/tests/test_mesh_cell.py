"""The four-chip cell end to end on 4 CPU devices at a small grid (Pallas
in interpret mode): the front door's own decomposition against the
reference split 2x2. A subprocess, as the benchmark's runs are."""

from bench.tests.runs import cache, run_cpu  # noqa: F401


def test_mesh_cell_end_to_end(cache):
    result, _ = run_cpu(cache, "star2d_r4_2x2.long", "64x256", 2**31 + 9,
                        0.5, 0, "none", 4)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert set(result["metrics"]) == {"gcells_per_s", "setup_s"}
    assert result["metrics"]["gcells_per_s"]["value"] > 0
