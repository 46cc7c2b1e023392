"""The fused executor's kernels compile for a described TPU v5e.

Nothing runs: each test lowers ``run_call`` (or the sharded ``run_fn`` on a
2x2 mesh) with ``interpret=False`` at the paper's sizes and the plan the
front door picks for the compiled backend, and lets the TPU compiler
(Mosaic) accept or refuse it — unaligned DMA windows, scoped-VMEM limits
and HBM overflow are refused here without a chip.  The topology is
described inside a fixture (only the worker that runs this file loads the
TPU library), and the tests skip where it cannot be described.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import repro
from repro.analysis.hw import V5E
from repro.configs import stencil2d, stencil3d
from repro.core import compat
from repro.core.distributed import Decomposition, DistributedStencil
from repro.core.program import StencilProgram
from repro.kernels import common
from repro.tuning import autotune

HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _assert_fits(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the executable"
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.1f} GiB exceeds the chip"


def _kernel_names(compiled):
    """The instruction names of the Pallas kernels, without the ``.N``
    the compiler appends: what a device trace calls them."""
    return {re.sub(r"\.\d+$", "", m.group(1)) for m in re.finditer(
        r"%([\w.-]+) = .*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text())}


def _compile_run_call(sharding, program, shape, variant):
    cs = repro.stencil(program).compile(shape, steps=1, plan="auto",
                                        cache=False, backend="pallas-tpu",
                                        variant=variant)
    assert cs.interpret is False
    plan = cs.plan
    args = (jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((program.num_neighbor_taps,), jnp.float32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding))
    compiled = common.run_call.lower(
        *args, program=program, plan=plan, true_shape=shape,
        interpret=False, rem=1, variant=variant).compile()
    _assert_fits(compiled)
    rem_variant = "plain" if variant == "temporal" else variant
    assert _kernel_names(compiled) == {f"stencil_superstep_{variant}",
                                       f"stencil_remainder_{rem_variant}"}
    return plan


@pytest.mark.parametrize("variant", ["plain", "pipelined", "temporal"])
def test_2d_r4_paper_compiles(one_chip, variant):
    w = stencil2d.workloads()["2d_r4_paper"]
    _compile_run_call(one_chip, w.spec, w.grid_shape, variant)


def test_3d_r4_paper_compiles(one_chip):
    w = stencil3d.workloads()["3d_r4_paper"]
    plan = _compile_run_call(one_chip, w.spec, w.grid_shape, "plain")
    assert plan.block_shape[-1] % 128 == 0      # 704 lanes round to tiles


@pytest.mark.parametrize("grid,steps,want", [
    ((696, 728, 696), 20, 32),          # 512-lane frame: four row tiles
    ((15680, 15680), 64, 8),            # 2048-lane frame: one row tile
])
def test_paper_plan_compiles_with_its_strip_rows(one_chip, grid, steps,
                                                 want):
    """Table III's r4 grids at the front door's plans: the 3D frame's
    strip loop computes several row tiles an iteration and still compiles
    within the launch's scoped VMEM (frames plus headroom); the 2D frame
    keeps one row tile a strip."""
    program = StencilProgram(ndim=len(grid), radius=4, shape="star",
                             boundary="clamp", dtype="float32")
    cs = repro.stencil(program).compile(grid, steps=steps, plan="auto",
                                        cache=False, backend="pallas-tpu")
    assert cs.kernel_strip_rows == want \
        == common.strip_rows(cs.plan.frame_shape())
    args = (jax.ShapeDtypeStruct(grid, jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((program.num_neighbor_taps,), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    compiled = common.run_call.lower(
        *args, program=program, plan=cs.plan, true_shape=grid,
        interpret=False, rem=steps % cs.plan.par_time,
        variant="plain").compile()
    _assert_fits(compiled)


def test_periodic_box_16384_compiles(one_chip):
    box = stencil2d.workloads()["2d_box_periodic_pod"].spec
    _compile_run_call(one_chip, box, (16384, 16384), "plain")


def _lower_mesh_run(topo, program, shape, steps):
    """Lower the sharded fused run for ``steps`` steps a call on the
    described 2x2 mesh, with the plan and split the mesh-aware tuner
    picks."""
    tuned = autotune(program, V5E, grid_shape=shape, backend="pallas-tpu",
                     n_devices=4, measure=False, cache=False)
    shards = tuned.decomp
    assert np.prod(shards) == 4
    names = tuple(f"d{i}" for i in range(program.ndim))
    mesh = compat.make_mesh(shards, names, devices=topo.devices)
    decomp = Decomposition(tuple((names[i],) if shards[i] > 1 else ()
                                 for i in range(program.ndim)))
    dist = DistributedStencil(program, program.default_coeffs(), tuned.plan,
                              mesh, decomp, shape, backend="pallas-tpu",
                              _warn=False)
    assert dist.interpret is False
    rep = NamedSharding(mesh, P())
    args = (jax.ShapeDtypeStruct(shape, jnp.float32,
                                 sharding=dist.sharding(0)),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((program.num_neighbor_taps,), jnp.float32,
                                 sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    rem = steps % tuned.plan.par_time
    return dist, rem, dist.run_fn(rem, 0).lower(*args), args


def test_2x2_mesh_run_compiles(topo):
    """The sharded fused run at one chip's share of the 2D pod config."""
    program = stencil2d.workloads()["2d_r4_pod"].spec
    _, rem, lowered, _ = _lower_mesh_run(topo, program, (8192, 8192), 1)
    assert rem == 1
    compiled = lowered.compile()
    _assert_fits(compiled)
    assert "collective-permute" in compiled.as_text()
    assert _kernel_names(compiled) == {"stencil_superstep_plain",
                                       "stencil_remainder_plain"}


def test_2x2_mesh_run_compiles_at_the_weak_scaled_paper_grid(topo):
    """The ``star2d_r4_2x2`` deployment: Table III's 2D r4 grid on each
    chip, 31360^2 in all, 64 steps a call.  Its exchange sends what
    ``exchange_bytes_per_superstep`` counts from the tile-aligned ring."""
    program = StencilProgram(ndim=2, radius=4, shape="star",
                             boundary="clamp", dtype="float32")
    dist, rem, lowered, args = _lower_mesh_run(topo, program,
                                               (31360, 31360), 64)
    # without a remainder: the loop body's two supersteps and the odd
    # tail's one
    full_only = lowered if rem == 0 else dist.run_fn(0, 0).lower(*args)
    permutes = [4 * int(np.prod([int(n) for n in dims.split(",")]))
                for dims in re.findall(
                    r"= f32\[([\d,]+)\]\S* collective-permute\(",
                    full_only.as_text(dialect="hlo"))]
    assert sum(permutes) == 3 * dist.exchange_bytes_per_superstep()
    compiled = lowered.compile()
    _assert_fits(compiled)
    assert "collective-permute" in compiled.as_text()
    assert "stencil_superstep_plain" in _kernel_names(compiled)
