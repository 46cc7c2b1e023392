"""Shared pytest fixtures.

NOTE: XLA_FLAGS / device counts are deliberately NOT set here — single-device
tests must see the real (1-CPU) topology.  Multi-device tests spawn
subprocesses (tests/dist_scripts/*) that set
``--xla_force_host_platform_device_count`` before importing jax.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
SCRIPTS = os.path.join(REPO, "tests", "dist_scripts")


def run_dist_script(name: str, *args: str, devices: int = 8,
                    timeout: int = 900) -> str:
    """Run tests/dist_scripts/<name> in a subprocess with N fake devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FORCE_DEVICES"] = str(devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, name), *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"{name} failed rc={proc.returncode}\n--- stdout ---\n"
            f"{proc.stdout[-4000:]}\n--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def dist_runner():
    return run_dist_script


@pytest.fixture
def hbm_traffic_only(monkeypatch):
    """Make interpret-mode ``cost_analysis`` count HBM traffic only.

    The padded-carry kernel steps its frames in VMEM with row-strip loops;
    under the interpreter those loops carry whole frame arrays, which the
    CPU cost analysis charges as memory traffic although on the chip they
    never leave VMEM.  The traffic guards replace the in-VMEM steps by a
    pass-through of the loaded frame: the frame DMA in, the block DMA out
    and every XLA-level op of the run are still counted.
    """
    from repro.kernels import common

    def frame_only(program, steps, center, taps, buf, *rest):
        return buf

    monkeypatch.setattr(common, "_fused_steps_vmem", frame_only)
