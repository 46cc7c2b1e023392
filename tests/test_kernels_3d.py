"""Pallas 3D stencil kernel vs pure-jnp oracle."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.backends import lower
from repro.core import reference as pref
from repro.core.blocking import SUBLANE, BlockPlan
from repro.core.program import StencilProgram
from repro.core.spec import StencilSpec
from repro.kernels import common, ops, ref


@pytest.mark.parametrize("rad", [1, 2, 3, 4])
@pytest.mark.parametrize("par_time", [1, 2])
def test_superstep_matches_oracle(rad, par_time):
    spec = StencilSpec(ndim=3, radius=rad)
    coeffs = spec.default_coeffs(seed=rad)
    plan = BlockPlan(spec=spec, block_shape=(8, 16, 128), par_time=par_time)
    g = ref.random_grid(spec, (20, 24, 200), seed=7)
    got = ops.stencil_superstep(g, spec, coeffs, plan)
    want = ref.stencil_nsteps_unrolled(spec, coeffs, g, par_time)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_non_divisible_3d():
    spec = StencilSpec(ndim=3, radius=2)
    coeffs = spec.default_coeffs(seed=2)
    plan = BlockPlan(spec=spec, block_shape=(8, 16, 128), par_time=2)
    g = ref.random_grid(spec, (11, 19, 140), seed=5)
    got = ops.stencil_superstep(g, spec, coeffs, plan)
    want = ref.stencil_nsteps_unrolled(spec, coeffs, g, 2)
    assert got.shape == g.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flops_accounting_3d():
    """BlockPlan.flops_per_block sums the shrinking valid regions of the
    interpreter's exact frames, and charges the compiled kernel par_time
    sweeps of its tile-rounded frame (planes r .. Z - r)."""
    spec = StencilSpec(ndim=3, radius=1)
    plan = BlockPlan(spec=spec, block_shape=(8, 16, 128), par_time=2)
    pz, py, px = plan.padded_shape
    want = 0
    for t in range(1, 3):
        want += (pz - 2 * t) * (py - 2 * t) * (px - 2 * t) \
            * spec.flops_per_cell
    assert plan.flops_per_block(compiled=False) == want
    # ring 2 rounds to (2, 8, 128): frame (12, 32, 384)
    assert plan.frame_shape() == (12, 32, 384)
    assert plan.flops_per_block() == 2 * (12 - 2) * 32 * 384 \
        * spec.flops_per_cell


# ---- taller row strips on narrow frames -------------------------------------
# ``common.strip_rows`` gives a frame whose lane tiles leave room in
# ``STRIP_VREGS`` a strip of several row tiles.  Interpret-mode frames are
# the exact-halo ``block + 2 * par_time * radius``: the cases below make
# their row counts 24, 72 (three strips of 24), 40 and 32 rows, and 28,
# which is not a whole number of row tiles and keeps 8-row strips with an
# overlapping last one.  Every grid is rounded up to whole blocks, and
# wide enough that periodic runs take the padded-carry kernels.

TALL_STRIP_CASES = {
    "r1-24rows": (1, 4, (8, 16, 128), (14, 14, 120)),
    "r2-72rows": (2, 2, (8, 64, 128), (14, 62, 120)),
    "r3-40rows": (3, 4, (8, 16, 128), (14, 14, 120)),
    "r4-32rows": (4, 2, (8, 16, 128), (14, 14, 120)),
    "r2-28rows-unaligned": (2, 3, (8, 16, 128), (14, 14, 120)),
}


@pytest.mark.parametrize("shape,want", [
    ((2048, 2048), SUBLANE),            # 2D paper frame, 16 lane tiles
    ((1600, 6528), SUBLANE),            # 2x2 mesh frame, 51 lane tiles
    ((64, 128, 512), 4 * SUBLANE),      # 3D paper frame, 4 lane tiles
    ((48, 512), 3 * SUBLANE),           # 6 row tiles: 3 divides them, 4 not
    ((37, 150), SUBLANE),               # interpret frame, unaligned rows
    ((4, 130), 4),                      # fewer rows than one tile
])
def test_strip_rows_rule(shape, want):
    assert common.strip_rows(shape) == want


def _padded_run(program, coeffs, plan, grid, steps):
    """``common.run_call`` in interpret mode, traced afresh so that the
    strip rule in force is the one its kernels are built with."""
    full, rem = divmod(steps, plan.par_time)
    fn = jax.jit(functools.partial(
        common.run_call.__wrapped__, program=program, plan=plan,
        true_shape=grid.shape, interpret=True, rem=rem))
    return np.asarray(fn(grid, coeffs.center, coeffs.taps,
                         jnp.int32(full)))


@pytest.mark.parametrize("boundary", ["clamp", "constant", "periodic"])
@pytest.mark.parametrize("case", list(TALL_STRIP_CASES))
def test_taller_strips_bit_identical(case, boundary, monkeypatch):
    """The padded-carry run with the strip rule's (taller) strips equals
    the same run with one row tile a strip, bit for bit, and the
    xla-reference lowering: shifting rows is exact and the taps keep
    their canonical order."""
    rad, par_time, block, shape = TALL_STRIP_CASES[case]
    program = StencilProgram(ndim=3, radius=rad, boundary=boundary,
                             boundary_value=0.25)
    coeffs = program.default_coeffs(seed=rad)
    plan = BlockPlan(spec=program, block_shape=block, par_time=par_time)
    grid = pref.random_grid(program, shape, seed=rad)
    steps = par_time + 1                  # one full superstep + remainder
    frame = plan.frame_shape(compiled=False)
    cs = repro.stencil(program, coeffs=coeffs).compile(
        shape, steps=steps, plan=plan, backend="pallas-interpret",
        cache=False)
    assert cs.kernel_strip_rows == common.strip_rows(frame)
    if frame[-2] % SUBLANE:
        assert cs.kernel_strip_rows == SUBLANE
    else:
        assert cs.kernel_strip_rows > SUBLANE

    tall = _padded_run(program, coeffs, plan, grid, steps)
    monkeypatch.setattr(common, "STRIP_VREGS", 0)
    assert common.strip_rows(frame) == SUBLANE
    one_tile = _padded_run(program, coeffs, plan, grid, steps)
    np.testing.assert_array_equal(tall, one_tile)
    oracle = lower(program, plan, coeffs=coeffs, backend="xla-reference")
    want = np.asarray(oracle.run(grid, steps))
    if rad > 1:
        np.testing.assert_array_equal(tall, want)
    else:
        # XLA:CPU compiles the r1 reference to sums that differ from the
        # interpreted kernel's in the last bits, at one row tile a strip
        # as well (the equality above carries the strip's bits).
        np.testing.assert_allclose(tall, want, atol=1e-6, rtol=1e-5)
