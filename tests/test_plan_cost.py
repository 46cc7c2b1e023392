"""The planner charges a compiled plan what the compiled kernel computes.

A compiled padded-carry launch DMAs its whole frame (the block plus a ring
rounded up to the register tile) and sweeps all of it at every fused step:
every row and lane, and in 3D the planes ``r .. Z - r``.  These tests hold
``BlockPlan``'s accounting to the kernel's own geometry
(``_launch_geometry``'s frame, the planes ``_apply_step`` hands its strip
loop), keep the interpreter on the exact-halo accounting, and pin what the
model planner picks for the paper's grids on the compiled backend.
"""

import math

import pytest

import repro
from repro import obs
from repro.analysis.hw import V5E
from repro.core.blocking import (BlockPlan, estimate, model_order,
                                 tile_alignment)
from repro.core.program import StencilProgram
from repro.kernels import common
from repro.tuning import enumerate_space, rank
from repro.tuning.model_rank import predict
from repro.tuning.space import Candidate

GRID_2D = (15680, 15680)
GRID_3D = (696, 728, 696)


def _star(ndim, radius=4):
    return StencilProgram(ndim=ndim, radius=radius, shape="star",
                          boundary="clamp")


def _swept(program, plan, compiled):
    """(frame shape, cells one ``_apply_step`` sweeps) of one launch,
    read off the kernel code: the frame ``_launch_geometry`` builds for
    the run's layout and the planes ``_apply_step`` gives its strip loop."""
    ndim = program.ndim
    layout = common.PaddedLayout(
        halo=plan.halo, local_shape=plan.block_shape,
        rounded=plan.block_shape,
        align=tile_alignment(ndim, compiled, program.dtype))
    frame, _ = common._launch_geometry(program, plan, layout, None)
    seen = []
    orig = common._for_each_strip
    common._for_each_strip = lambda f, body, planes=None: seen.append(
        (f, planes))
    try:
        common._apply_step(program, None, None, None, None, frame)
    finally:
        common._for_each_strip = orig
    ((f, planes),) = seen
    rows, lanes = f.shape[-2], f.shape[-1]
    if compiled:
        assert rows % f.strip == 0      # the strips cover the rows exactly
    depth = 1 if ndim == 2 else (planes[1] - planes[0])
    return f.shape, depth * rows * lanes


@pytest.mark.parametrize("ndim,block,par_time", [
    (2, (1968, 1792), 9),
    (3, (144, 96, 128), 4),
    (3, (48, 112, 256), 2),
])
def test_compiled_charge_is_the_kernel_sweep(ndim, block, par_time):
    program = _star(ndim)
    plan = BlockPlan(spec=program, block_shape=block, par_time=par_time)
    frame, per_sweep = _swept(program, plan, compiled=True)
    assert plan.frame_shape() == frame
    assert plan.cells_per_block() == par_time * per_sweep
    assert plan.flops_per_block() \
        == par_time * per_sweep * program.flops_per_cell
    assert plan.hbm_bytes_per_block() \
        == 4 * (math.prod(frame) + math.prod(block))
    assert plan.useful_fraction_for(True) == pytest.approx(
        math.prod(block) / per_sweep)


def test_old_3d_plan_frame_and_redundancy():
    """The plan the exact-halo model picked for the paper's 3D grid: a
    144x96x128 block in a 176x128x384 frame, 168 planes swept a step."""
    plan = BlockPlan(spec=_star(3), block_shape=(144, 96, 128), par_time=4)
    assert plan.frame_shape() == (176, 128, 384)
    assert plan.cells_per_block() == 4 * 168 * 128 * 384
    assert plan.useful_fraction == pytest.approx(0.4909, abs=1e-4)
    assert plan.useful_fraction_for(True) == pytest.approx(0.2143, abs=1e-4)
    assert plan.compute_redundancy(GRID_3D) == pytest.approx(5.62, abs=0.01)


@pytest.mark.parametrize("ndim,block,par_time", [
    (2, (256, 512), 3),
    (3, (16, 32, 128), 2),
])
def test_interpreter_keeps_exact_ring_accounting(ndim, block, par_time):
    program = _star(ndim, radius=2)
    plan = BlockPlan(spec=program, block_shape=block, par_time=par_time)
    frame, _ = _swept(program, plan, compiled=False)
    assert plan.frame_shape(compiled=False) == plan.padded_shape == frame
    r = program.halo_radius
    assert plan.cells_per_block(compiled=False) == sum(
        math.prod(p - 2 * t * r for p in plan.padded_shape)
        for t in range(1, par_time + 1))
    assert plan.useful_fraction_for(False) == plan.useful_fraction
    assert plan.hbm_bytes_per_block(compiled=False) == 4 * (
        math.prod(plan.padded_shape) + math.prod(block))
    # the cost follows the candidate's backend
    for name, compiled in (("pallas-interpret", False),
                           ("pallas-tpu", True)):
        cand = Candidate(plan=plan, backend=name, backend_version=1,
                         halo_aligned=False)
        assert cand.compiled is compiled
        got = predict(program, cand, V5E)
        want = estimate(plan, V5E, compiled)
        assert got.predicted_gcells == pytest.approx(want.gcells_per_s / 1e9)


def test_compiled_superstep_moves_only_its_dmas():
    """Compiled: frame reads and block writes; the interpreter's model
    also passes over both padded carry buffers."""
    program = _star(3)
    plan = BlockPlan(spec=program, block_shape=(48, 112, 256), par_time=2)
    blocks = plan.blocks_per_superstep(GRID_3D)
    assert blocks == 15 * 7 * 3
    assert plan.run_bytes_per_superstep(GRID_3D) \
        == blocks * plan.hbm_bytes_per_block()
    assert plan.run_bytes_per_superstep(GRID_3D, compiled=False) \
        > blocks * plan.hbm_bytes_per_block(compiled=False)


def test_model_redundancy_counter_on_the_compiled_stencil():
    """``model_compute_redundancy`` is on the CompiledStencil and in the
    ``repro.compile`` span; nothing runs."""
    plan = BlockPlan(spec=_star(3), block_shape=(144, 96, 128), par_time=4)
    with obs.profile() as rec:
        cs = repro.stencil(_star(3)).compile(GRID_3D, steps=20, plan=plan,
                                             backend="pallas-tpu")
    assert cs.interpret is False
    assert cs.model_compute_redundancy == pytest.approx(5.62, abs=0.01)
    (span,) = rec.spans("compile")
    assert span["model_compute_redundancy"] == cs.model_compute_redundancy
    assert span["kernel_strip_rows"] == cs.kernel_strip_rows \
        == common.strip_rows(plan.frame_shape())
    assert span["model_bytes_per_superstep"] \
        == plan.run_bytes_per_superstep(GRID_3D)


@pytest.mark.parametrize("grid,backend,want", [
    (GRID_2D, "pallas-tpu", 8),         # 2048-lane frame: one row tile
    (GRID_3D, "pallas-tpu", 32),        # 512-lane frame: four row tiles
    ((20, 40, 200), "xla-reference", None),     # no strip kernel
])
def test_kernel_strip_rows_counter_on_the_compile_span(grid, backend, want):
    """``kernel_strip_rows`` is on the CompiledStencil and in the
    ``repro.compile`` span; nothing runs."""
    with obs.profile() as rec:
        cs = repro.stencil(_star(len(grid))).compile(
            grid, steps=20, backend=backend, cache=False)
    (span,) = rec.spans("compile")
    assert span["kernel_strip_rows"] == cs.kernel_strip_rows == want
    if want is not None:
        assert want == common.strip_rows(cs.plan.frame_shape())


def test_compiled_3d_paper_plan_sweeps_at_most_3_3x():
    cs = repro.stencil(_star(3)).compile(GRID_3D, steps=20,
                                         backend="pallas-tpu", cache=False)
    assert cs.interpret is False and cs.variant == "plain"
    assert cs.model_compute_redundancy <= 3.3
    assert cs.plan.block_shape == (48, 112, 256) and cs.plan.par_time == 2


def test_compiled_2d_paper_plan_keeps_its_frame():
    """2D keeps the 1968x1792 block in its 2048x2048 frame.  par_time 9
    and 10 share that frame and redundancy, so the model prices them
    equally; the tie goes to 10, which moves fewer HBM bytes a step."""
    cs = repro.stencil(_star(2)).compile(GRID_2D, steps=64,
                                         backend="pallas-tpu", cache=False)
    assert cs.variant == "plain"
    assert cs.plan.block_shape == (1968, 1792)
    assert cs.plan.frame_shape() == (2048, 2048)
    assert cs.plan.par_time == 10
    program = _star(2)
    ranked = rank(program, [c for c in enumerate_space(
        program, V5E, backends=["pallas-tpu"], grid_shape=GRID_2D)
        if c.plan.block_shape == (1968, 1792)
        and c.plan.par_time in (9, 10)], V5E, grid_shape=GRID_2D)
    pt10, pt9 = ranked
    assert (pt10.candidate.par_time, pt9.candidate.par_time) == (10, 9)
    assert model_order(pt10.predicted_gbps, 0, False, 0)[0] \
        == model_order(pt9.predicted_gbps, 0, False, 0)[0]
    assert pt10.hbm_bytes_per_cell < pt9.hbm_bytes_per_cell


def test_model_order_ties_within_float_rounding():
    a = model_order(2703.935513994108, 1.0, True, 10)
    b = model_order(2703.9355139941076, 0.5, False, 20)
    assert b > a        # equal rates: fewer bytes a cell wins
    assert model_order(2704.0, 9.0, False, 99) > a


def test_compiled_overlap_tax_prunes_the_space():
    """The compiled space prunes on the fraction of the kernel's frame."""
    program = _star(3)
    cands = enumerate_space(program, V5E, backends=["pallas-tpu"],
                            grid_shape=GRID_3D)
    assert cands
    assert all(c.plan.useful_fraction_for(True) > 0.25 for c in cands)
    assert not any(c.plan.block_shape == (144, 96, 128)
                   and c.plan.par_time == 4 for c in cands)


def test_compiled_redundancy_of_a_run_matches_the_frame_count():
    """compute_redundancy is launches x swept cells over useful cells."""
    program = _star(2, radius=1)
    plan = BlockPlan(spec=program, block_shape=(16, 128), par_time=2)
    grid = (37, 300)
    launches = 3 * 3
    frame = (16 + 16, 128 + 256)
    assert plan.compute_redundancy(grid) == pytest.approx(
        launches * math.prod(frame) / math.prod(grid))
    assert plan.compute_redundancy(grid, compiled=False) \
        < plan.compute_redundancy(grid)
