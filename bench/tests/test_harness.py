"""The harness end to end on the CPU at small grids (Pallas in interpret
mode, past its look for a chip), and its refusal of a host without a TPU.
Each run is a subprocess, as the benchmark's runs are."""

import os
import shutil
import subprocess
import sys

from bench.tests.runs import ROOT, cache, check_lines, env, run_cpu  # noqa: F401


def test_long_cell_end_to_end(cache):
    result, err = run_cpu(cache, "star2d_r4_paper.long", "64x256",
                         2**33 + 17, 1, 0)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"gcells_per_s", "setup_s"}
    assert result["metrics"]["gcells_per_s"]["unit"] == "Gcell/s"
    assert result["metrics"]["gcells_per_s"]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    # the numbers compared close standard error, each with its limit
    assert check_lines(err) == [
        f"check {k} = {v['value']!r} limit {v['limit']!r}"
        for k, v in result["checks"].items()]
    assert err.strip().splitlines()[-1].startswith("check ")


def test_step1_cell_traced_reads_receivers(cache):
    result, _ = run_cpu(cache, "star2d_r4_paper.step1", "64x256", 5, 1,
                        1)
    assert result["correct"] is True
    assert set(result["checks"]) == {"grid_rel_err", "receiver_rel_err"}
    # no device trace on the CPU: only the host-clock set-up spans report
    assert set(result["metrics"]) == {"setup.plan_s", "setup.first_call_s"}
    assert "busy_s" not in result["device"]


def test_step1_cell_reports_its_own_rate(cache):
    result, _ = run_cpu(cache, "star2d_r4_paper.step1", "64x256", 6, 1, 0)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"gcells_per_s.step1", "call_p95_ms",
                                      "setup_s"}
    assert result["metrics"]["gcells_per_s.step1"]["value"] > 0


def test_no_tpu_exits_without_a_result(cache):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "star2d_r4_paper.long", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env(cache),
        cwd=ROOT)
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_benchmark_files_alone_give_no_result(cache, tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".traces",
                                                  "__pycache__"))
    environ = env(cache)
    environ.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "star2d_r4_paper.long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=environ, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_call_profile_splits_the_longest_calls():
    from bench import harness
    ms = [(1, 2, 27), (1, 2, 27), (1, 100, 29), (1, 2, 28)]
    parts = [tuple(v / 1e3 for v in p) for p in ms]
    w = harness.Window(calls=4, durations=[sum(p) for p in parts],
                       parts=parts, seconds=0.221, readouts=[], held=None,
                       held_index=1, out=None, compiles=0)
    line = harness.call_profile(w, longest=1)
    assert "median 30.500" in line and "max 130.000" in line
    assert "1 calls over 1.05 x median hold 0.100 s" in line
    assert "#3 130.00 = dispatch 1.00 + read 100.00 + block 29.00" in line
    assert line.endswith("block 29.00")
