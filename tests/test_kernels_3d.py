"""Pallas 3D stencil kernel vs pure-jnp oracle."""

import numpy as np
import pytest

from repro.core.blocking import BlockPlan
from repro.core.spec import StencilSpec
from repro.kernels import ops, ref


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
