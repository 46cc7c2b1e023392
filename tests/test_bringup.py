"""CPU-side guards of the chip bring-up.

* ``compat.tracing`` recognises tracers on the installed JAX;
* a compiled backend never drops to the interpreter, on the mesh path too;
* the planner's chip comes from the device kind, and an unknown kind fails;
* ``stencil_serve`` ``main`` exits non-zero when requests failed;
* the compile-cache helper honours ``JAX_COMPILATION_CACHE_DIR`` and
  otherwise uses the fixed in-checkout path;
* ``chip_smoke.py`` refuses a host without a TPU and rejects a corrupted
  result.
"""

import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hw
from repro.core import compat
from repro.core.blocking import BlockPlan
from repro.core.distributed import Decomposition, DistributedStencil
from repro.core.program import StencilProgram
from repro.launch import compile_cache, stencil_serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(REPO)


def test_tracing_is_true_only_for_tracers():
    seen = []

    @jax.jit
    def f(x):
        seen.append(compat.tracing(x))
        return x + 1

    f(jnp.ones(3))
    assert seen == [True]
    assert not compat.tracing(jnp.ones(3))


def test_mesh_executor_keeps_compiled_backend_compiled():
    """backend="pallas-tpu" on a host without a TPU stays interpret=False
    on the sharded executor and fails, instead of interpreting."""
    if jax.default_backend() == "tpu":
        pytest.skip("needs a host without a TPU")
    prog = StencilProgram(ndim=2, radius=1)
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    mesh = compat.make_mesh((1,), ("x",))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        dist = DistributedStencil(prog, prog.default_coeffs(), plan, mesh,
                                  Decomposition((("x",), ())), (32, 128),
                                  backend="pallas-tpu")
    assert dist.interpret is False
    with pytest.raises(Exception):
        jax.block_until_ready(dist.run(jnp.zeros((32, 128), jnp.float32),
                                       4))


def test_chip_table_by_device_kind():
    assert hw.chip_for_kind("TPU v5 lite") is hw.V5E
    with pytest.raises(ValueError, match="no TpuChip entry"):
        hw.chip_for_kind("TPU v99")
    from repro.executor import local_chip
    if jax.default_backend() != "tpu":
        assert local_chip() is hw.V5E


def test_serve_main_exit_status(monkeypatch, capsys):
    # keep this process cache-free: main() would turn the cache on
    monkeypatch.setattr(stencil_serve, "enable_compile_cache", lambda: "")
    args = ["--requests", "2", "--grid", "16,128", "--radius", "1",
            "--steps", "2", "--max-batch", "2"]
    assert stencil_serve.main(args) == 0

    def refuse(self, *a, **k):
        raise RuntimeError("no plan")

    monkeypatch.setattr(stencil_serve.StencilServer, "_compiled_for",
                        refuse)
    assert stencil_serve.main(args) == 1
    assert "FAILED: RuntimeError: no plan" in capsys.readouterr().out


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, tmp_path,
                                   restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_chip_smoke_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_smoke_rejects_corrupted_results(chip_smoke):
    prog = StencilProgram(ndim=2, radius=2)
    coeffs = prog.default_coeffs()
    grid = jnp.asarray(np.random.RandomState(0).uniform(-1, 1, (64, 160)),
                       jnp.float32)
    steps = 3
    from repro.core.reference import program_nsteps
    good = program_nsteps(prog, coeffs, grid, steps)
    chip_smoke.check_full("good", good, good)
    chip_smoke.check_oracle("good", prog, coeffs, grid, good, steps, 16)
    bad = good.at[0, 0].add(1e-3)
    with pytest.raises(AssertionError):
        chip_smoke.check_full("bad", bad, good)
    with pytest.raises(AssertionError):
        chip_smoke.check_oracle("bad", prog, coeffs, grid, bad, steps, 16)
