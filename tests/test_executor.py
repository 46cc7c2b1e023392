"""The one front door: ``repro.stencil(...).compile(...).run(...)``.

ISSUE 5 regressions:
  * parity — the unified executor is bit-identical to the legacy entry
    points across radii 1-4 x {2D, 3D} x {fused, batched, pipelined}
    (the sharded host-mesh leg lives in
    ``tests/dist_scripts/stencil_executor_dist.py``) and tracks the
    independent numpy oracle;
  * executable caching — repeated ``run`` calls and same-remainder step
    counts hit ONE compile (``common.trace_count``), and ``plan="auto"``
    hits the persistent plan cache on the second ``compile()``;
  * validation — ``steps >= 1`` and batch-rank mismatches are rejected at
    the API boundary with actionable messages instead of surfacing as
    shape errors deep inside Pallas;
  * the legacy surfaces (``StencilEngine``, ``ops.stencil_run``,
    ``DistributedStencil``) warn as deprecated but stay bit-compatible;
  * the public package surface (``repro.__all__``, ``__version__``) and
    the deprecation audit stay green.
"""

import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

import repro
from repro.core import reference as ref
from repro.core.blocking import BlockPlan
from repro.core.program import StencilProgram
from repro.kernels import common, ops

TOL = dict(atol=5e-4, rtol=5e-4)

BLOCKS = {2: (16, 128), 3: (8, 16, 128)}
GRIDS = {2: (37, 150), 3: (9, 18, 140)}     # non-divisible by the blocks


def _legacy_run(*args, **kwargs):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return ops.stencil_run(*args, **kwargs)


# ---- parity vs the legacy entry points -------------------------------------

@pytest.mark.parametrize("ndim", [2, 3])
@pytest.mark.parametrize("rad", [1, 2, 3, 4])
def test_executor_parity_fused_batched_pipelined(ndim, rad):
    """radii 1-4 x {2D, 3D}: the front door's fused, batched, and pipelined
    executables are bit-identical to the legacy ``ops.stencil_run`` calls
    they replace, and track the float64 numpy oracle."""
    boundary = ("clamp", "periodic", "constant")[rad % 3]
    prog = StencilProgram(ndim=ndim, radius=rad, boundary=boundary,
                          boundary_value=0.25)
    coeffs = prog.default_coeffs(seed=rad)
    plan = BlockPlan(spec=prog, block_shape=BLOCKS[ndim], par_time=2)
    G = GRIDS[ndim]
    g = ref.random_grid(prog, G, seed=rad)
    steps = 5                       # full=2, rem=1
    sten = repro.stencil(prog, coeffs=coeffs)

    # fused
    cs = sten.compile(G, steps=steps, plan=plan)
    got = cs.run(g)
    want = _legacy_run(g, prog, coeffs, plan, steps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    oracle = ref.numpy_program_nsteps(prog, coeffs, g, steps)
    np.testing.assert_allclose(np.asarray(got), oracle, **TOL)

    # pipelined (double-buffered prefetch kernel via the -pipelined backend)
    cs_p = sten.compile(G, steps=steps, plan=plan, pipelined=True)  # legacy-ok
    assert cs_p.backend.endswith("-pipelined")
    got_p = cs_p.run(g)
    want_p = _legacy_run(g, prog, coeffs, plan, steps, pipelined=True)  # legacy-ok
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))

    # batched (B, *grid)
    B = 2
    gb = jnp.stack([ref.random_grid(prog, G, seed=s) for s in range(B)])
    cs_b = sten.compile(G, steps=steps, plan=plan, batch=B)
    got_b = cs_b.run(gb)
    want_b = _legacy_run(gb, prog, coeffs, plan, steps)
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))


def test_executor_xla_reference_dispatch():
    """backend="xla-reference" routes through the oracle lowering (no
    pallas executable is built) and matches the numpy oracle."""
    prog = StencilProgram(ndim=2, radius=2)
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    g = ref.random_grid(prog, (23, 37), seed=7)
    common.reset_trace_counts()
    cs = repro.stencil(prog).compile((23, 37), steps=5, plan=plan,
                                     backend="xla-reference")
    out = cs.run(g)
    assert common.trace_count("run_call") == 0
    want = ref.numpy_program_nsteps(prog, cs.coeffs, g, 5)
    np.testing.assert_allclose(np.asarray(out), want, **TOL)


@pytest.mark.slow
def test_executor_sharded_host_mesh(dist_runner):
    """Sharded parity + trace counts + auto-decomposition on 8 fake
    devices (subprocess so the device count is set before jax imports)."""
    out = dist_runner("stencil_executor_dist.py")
    markers = [f"parity_{nd}d_r{r}" for nd in (2, 3) for r in (1, 2, 3, 4)]
    markers += ["trace_counts", "batched_sharded", "pipelined_sharded",
                "auto_decomp", "pinned_infeasible", "pinned_backend_mode",
                "donate", "all"]
    for marker in markers:
        assert f"OK {marker}" in out, marker


# ---- executable + plan caching ---------------------------------------------

def test_one_compile_per_remainder_and_repeated_runs():
    """Repeated .run() calls and any steps = k*par_time + rem with the same
    remainder share ONE executable; a new remainder adds exactly one."""
    prog = StencilProgram(ndim=2, radius=1)
    plan = BlockPlan(spec=prog, block_shape=(8, 128), par_time=3)
    g = ref.random_grid(prog, (22, 141), seed=2)  # shape unique to this test
    cs = repro.stencil(prog).compile((22, 141), steps=3 * 3 + 2, plan=plan)

    common.reset_trace_counts()
    cs.run(g)
    cs.run(g)                       # repeated run: cache hit
    cs.run(g, steps=5 * 3 + 2)      # same remainder: cache hit
    cs.run(g, steps=2)              # full=0, rem=2: still the same rem
    assert common.trace_count("run_call") == 1
    cs.run(g, steps=6)              # rem=0: the one legitimate new compile
    assert common.trace_count("run_call") == 2


def test_batch_rank_is_a_separate_executable_not_a_retrace_storm():
    prog = StencilProgram(ndim=2, radius=1)
    plan = BlockPlan(spec=prog, block_shape=(8, 128), par_time=2)
    G = (19, 143)                   # shape unique to this test
    g = ref.random_grid(prog, G, seed=3)
    gb = jnp.stack([g, g, g])
    sten = repro.stencil(prog)
    cs = sten.compile(G, steps=4, plan=plan)
    cs_b = sten.compile(G, steps=4, plan=plan, batch=3)
    common.reset_trace_counts()
    cs.run(g)
    cs_b.run(gb)
    assert common.trace_count("run_call") == 2
    cs.run(g)
    cs_b.run(gb)
    assert common.trace_count("run_call") == 2


def test_plan_auto_hits_plan_cache_on_second_compile(tmp_path):
    prog = StencilProgram(ndim=2, radius=2)
    path = str(tmp_path / "plans.json")
    kw = dict(steps=4, plan="auto", max_par_time=2, cache_path=path)
    cs1 = repro.stencil(prog).compile((48, 256), **kw)
    assert cs1.tuned is not None
    assert not cs1.from_plan_cache
    cs2 = repro.stencil(prog).compile((48, 256), **kw)
    assert cs2.from_plan_cache
    assert cs2.plan == cs1.plan
    assert cs2.backend == cs1.backend


def test_cost_metadata():
    prog = StencilProgram(ndim=3, radius=2)
    plan = BlockPlan(spec=prog, block_shape=(8, 16, 128), par_time=2)
    cs = repro.stencil(prog).compile((16, 32, 256), steps=4, plan=plan)
    assert cs.plan is plan
    assert cs.decomp is None
    assert cs.devices == 1
    assert cs.cost.predicted_gbps > 0
    assert cs.cost.predicted_gflops > 0
    assert cs.cost.bound in ("compute", "memory")
    assert cs.backend in repro.available_backends()


# ---- compile/run validation ------------------------------------------------

def test_compile_rejects_bad_steps():
    prog = StencilProgram(ndim=2, radius=1)
    sten = repro.stencil(prog)
    for bad in (0, -3, 1.5, "4", None, True):
        with pytest.raises(ValueError, match="steps must be an int >= 1"):
            sten.compile((16, 128), steps=bad)


def test_compile_rejects_bad_grid_shape_and_batch():
    prog = StencilProgram(ndim=2, radius=1)
    sten = repro.stencil(prog)
    with pytest.raises(ValueError, match="2-D program"):
        sten.compile((8, 16, 128), steps=2)
    with pytest.raises(ValueError, match="positive extents"):
        sten.compile((0, 128), steps=2)
    with pytest.raises(ValueError, match="batch must be None"):
        sten.compile((16, 128), steps=2, batch=0)
    with pytest.raises(ValueError, match="batch must be None"):
        sten.compile((16, 128), steps=2, batch=2.5)


def test_run_rejects_batch_rank_mismatch_with_actionable_messages():
    prog = StencilProgram(ndim=2, radius=1)
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    sten = repro.stencil(prog)
    g = jnp.zeros((16, 128), jnp.float32)
    gb = jnp.stack([g, g])

    cs = sten.compile((16, 128), steps=2, plan=plan)
    with pytest.raises(ValueError, match=r"compile\(batch=2\)"):
        cs.run(gb)                  # batched grid into unbatched executable
    with pytest.raises(ValueError, match="does not match the compiled"):
        cs.run(jnp.zeros((32, 128), jnp.float32))

    cs_b = sten.compile((16, 128), steps=2, plan=plan, batch=3)
    with pytest.raises(ValueError, match="compiled for batch=3"):
        cs_b.run(g)                 # unbatched grid into batched executable
    with pytest.raises(ValueError, match="batch=3"):
        cs_b.run(gb)                # wrong batch extent
    with pytest.raises(ValueError, match="steps must be an int >= 1"):
        cs.run(g, steps=0)


def test_compile_rejects_bad_plan_backend_devices():
    prog = StencilProgram(ndim=2, radius=1)
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    sten = repro.stencil(prog)
    with pytest.raises(ValueError, match='plan must be "auto", "model"'):
        sten.compile((16, 128), steps=2, plan="fastest")
    with pytest.raises(KeyError, match="unknown backend"):
        sten.compile((16, 128), steps=2, plan=plan, backend="verilog")
    with pytest.raises(ValueError, match="no pipelined lowering"):
        sten.compile((16, 128), steps=2, plan=plan,
                     backend="xla-reference", pipelined=True)  # legacy-ok
    with pytest.raises(ValueError, match="cannot run sharded"):
        sten.compile((16, 128), steps=2, plan=plan,
                     backend="xla-reference", devices=2)
    with pytest.raises(ValueError, match="shard count per grid axis"):
        sten.compile((16, 128), steps=2, plan=plan, devices=(2, 2, 2))
    # single-device hosts: asking for a mesh must name the XLA_FLAGS fix
    with pytest.raises(ValueError, match="visible devices"):
        sten.compile((16, 128), steps=2, plan=plan, devices=1024)


def test_pinned_compiled_backend_does_not_silently_interpret():
    """backend="pallas-tpu" pins interpret=False (the backend's declared
    mode): on a host that cannot compile it the run FAILS like the legacy
    registry lowering did, instead of silently running the interpreter."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("needs a non-TPU host")
    prog = StencilProgram(ndim=2, radius=1)
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    cs = repro.stencil(prog).compile((16, 128), steps=2, plan=plan,
                                     backend="pallas-tpu")
    assert cs.interpret is False
    with pytest.raises(Exception):
        cs.run(jnp.zeros((16, 128), jnp.float32))


def test_plan_model_matches_planner():
    from repro.core.blocking import plan_blocking
    prog = StencilProgram(ndim=2, radius=1)
    cs = repro.stencil(prog).compile((20, 140), steps=2, plan="model",
                                     max_par_time=2)
    want = plan_blocking(prog, grid_shape=(20, 140), max_par_time=2,
                         compiled=not cs.interpret).plan
    assert cs.plan == want
    assert cs.tuned is None and not cs.from_plan_cache


# ---- legacy shims: deprecated but bit-compatible ---------------------------

def test_legacy_stencil_run_warns_and_matches():
    prog = StencilProgram(ndim=2, radius=2)
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    g = ref.random_grid(prog, (26, 139), seed=4)
    cs = repro.stencil(prog).compile((26, 139), steps=5, plan=plan)
    with pytest.warns(DeprecationWarning, match="stencil_run is deprecated"):
        legacy = ops.stencil_run(g, prog, cs.coeffs, plan, 5)
    np.testing.assert_array_equal(np.asarray(cs.run(g)), np.asarray(legacy))


def test_legacy_engine_warns_and_matches():
    from repro.core.temporal import StencilEngine
    prog = StencilProgram(ndim=2, radius=1, boundary="periodic")
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    coeffs = prog.default_coeffs(seed=6)
    g = ref.random_grid(prog, (18, 131), seed=6)
    with pytest.warns(DeprecationWarning, match="StencilEngine"):
        eng = StencilEngine(spec=prog, coeffs=coeffs, plan=plan)
    got = eng.run(g, 5)
    cs = repro.stencil(prog, coeffs=coeffs).compile((18, 131), steps=5,
                                                    plan=plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(cs.run(g)))
    assert eng.run(g, 0) is g       # historical steps=0 identity


def test_legacy_distributed_warns_on_direct_construction():
    from repro.core import compat
    from repro.core.distributed import Decomposition, DistributedStencil
    prog = StencilProgram(ndim=2, radius=1)
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    mesh = compat.make_mesh((1, 1), ("r", "c"))
    with pytest.warns(DeprecationWarning, match="DistributedStencil"):
        DistributedStencil(prog, prog.default_coeffs(), plan, mesh,
                           Decomposition(((), ())), (16, 128))


def test_custom_registered_backend_lowering_is_executed():
    """A third-party backend registered through the public registry runs
    its OWN lowering on the single-device path — the built-in pallas fast
    path never silently replaces it."""
    from repro.backends import (BackendTraits, LoweredStencil,
                                register_backend)
    calls = []

    @register_backend("test-custom", traits=BackendTraits(local_kernel=True))
    def _custom(program, plan, coeffs):
        def superstep_fn(grid, c):
            return grid

        def run_fn(grid, c, steps):
            calls.append(steps)
            return ref.program_nsteps_unrolled(program, c, grid, steps)

        return LoweredStencil(program, plan, coeffs, superstep_fn, run_fn)

    prog = StencilProgram(ndim=2, radius=1)
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    g = ref.random_grid(prog, (16, 128), seed=1)
    cs = repro.stencil(prog).compile((16, 128), steps=2, plan=plan,
                                     backend="test-custom")
    out = cs.run(g)
    assert calls == [2], "custom lowering was bypassed"
    want = ref.numpy_program_nsteps(prog, cs.coeffs, g, 2)
    np.testing.assert_allclose(np.asarray(out), want, **TOL)


def test_numpy_integer_arguments_accepted():
    prog = StencilProgram(ndim=2, radius=1)
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    cs = repro.stencil(prog).compile(
        (np.int64(16), np.int64(128)), steps=np.int64(4),
        batch=np.int32(2), devices=np.int64(1), plan=plan)
    assert (cs.grid_shape, cs.steps, cs.batch) == ((16, 128), 4, 2)
    out = cs.run(np.zeros((2, 16, 128), np.float32), steps=np.int64(2))
    assert out.shape == (2, 16, 128)


def test_server_shares_executables_across_step_counts():
    """StencilServer keys executables by (program, shape, batch) only —
    flushes with different step counts reuse one CompiledStencil (and so
    the per-remainder executable table behind it) instead of recompiling
    the serving hot path per step count."""
    from repro.launch.stencil_serve import StencilServer
    prog = StencilProgram(ndim=2, radius=1)
    server = StencilServer(max_batch=4, max_par_time=2)
    rng = np.random.RandomState(5)
    for steps in (5, 7, 9):        # same remainder at any par_time <= 2
        server.submit(prog, rng.uniform(-1, 1, (20, 138)), steps=steps)
        assert not server.failed
        server.flush()
    assert len(server._compiled) == 1
    assert len(server._resolved) == 1


# ---- package surface + audit -----------------------------------------------

def test_public_surface_and_version():
    assert repro.__version__ == "0.3.0"
    for name in repro.__all__:
        assert getattr(repro, name, None) is not None, name
    from repro import executor
    assert repro.stencil is executor.stencil
    assert isinstance(repro.stencil(StencilProgram(ndim=2, radius=1)),
                      repro.Stencil)


def test_deprecation_audit_is_clean():
    """The committed tree passes the CI deprecation audit (no legacy entry
    points in examples/, benchmarks/, configs, or the serving launcher)."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "deprecation_audit.py")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
