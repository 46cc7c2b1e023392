"""Shared by the run tests: a subprocess run of ``cpu_run.py`` on the CPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CPU_RUN = os.path.join(ROOT, "bench", "tests", "cpu_run.py")


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """One compilation cache for a module's runs, as a checkout has."""
    return tmp_path_factory.mktemp("jax_cache")


def env(cache_dir):
    out = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    out.pop("XLA_FLAGS", None)
    return out


def run_cpu(cache_dir, *args, timeout=600):
    """(result, stderr) of ``cpu_run.py`` with ``args``."""
    proc = subprocess.run([sys.executable, CPU_RUN, *map(str, args)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env(cache_dir), cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def check_lines(stderr):
    return [ln for ln in stderr.strip().splitlines()
            if ln.startswith("check ")]
