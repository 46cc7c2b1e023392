"""Quickstart: high-order heat diffusion through the one front door.

Describes a radius-4 2D stencil (paper's hardest 2D case) as a
``StencilProgram``, compiles it through the unified executor —
``repro.stencil(program).compile(grid_shape, steps=...)`` — which resolves
the blocking plan (autotuner + plan cache), the backend, and the
performance-model cost, then runs it and verifies against the naive
reference.  The legacy entry points (``StencilEngine``,
``kernels.ops.stencil_run``, ``DistributedStencil``) are deprecated shims
over this same executor.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

import repro
from repro.analysis.hw import V5E
from repro.core.reference import program_nsteps_unrolled, random_grid


def main():
    program = repro.StencilProgram(ndim=2, radius=4, shape="star",
                                   boundary="clamp")
    print(f"program: 2D star radius={program.radius}  "
          f"taps={program.num_taps}  "
          f"FLOP/cell={program.flops_per_cell} (paper Table I: 33)")

    # one front door: plan="auto" searches the legal (bsize, par_time)
    # space, ranks by the roofline model, and caches the winner — the
    # second compile for this (program, grid, chip, backend) is a cache hit
    grid_shape = (256, 512)
    steps = 8
    cs = repro.stencil(program).compile(grid_shape, steps=steps,
                                        plan="auto", max_par_time=4)
    plan = cs.plan
    print(f"backend: {cs.backend} v{cs.backend_version}"
          f"{'  [plan cache]' if cs.from_plan_cache else ''}")
    print(f"plan: block={plan.block_shape} par_time={plan.par_time} "
          f"halo={plan.halo} vmem={plan.vmem_bytes / 2**20:.1f} MiB")

    est = cs.cost
    print(f"v5e model: {est.predicted_gcells:.0f} GCell/s "
          f"{est.predicted_gflops:.0f} GFLOP/s ({est.bound}-bound), "
          f"effective {est.predicted_gbps:.0f} GB/s"
          f" vs {V5E.hbm_bytes_per_s / 1e9:.0f} GB/s HBM")

    grid = random_grid(program, grid_shape, seed=0)
    out = cs.run(grid)
    want = program_nsteps_unrolled(program, cs.coeffs, grid, steps)
    err = float(jnp.max(jnp.abs(out - want)))
    assert np.allclose(out, want, atol=1e-4), err
    print(f"{steps} steps via temporal blocking == naive reference "
          f"(max err {err:.2e})  OK")

    # kernel variants ride the same front door: variant="temporal" fuses a
    # whole superstep chunk into each launch (one VMEM-resident window, a
    # fraction of the plain per-superstep HBM traffic), bit-for-bit the
    # same arithmetic as the plain kernel
    cst = repro.stencil(program).compile(grid_shape, steps=steps,
                                         plan=plan, variant="temporal")
    outt = cst.run(grid)
    assert np.allclose(np.asarray(outt), np.asarray(out),
                       atol=1e-6, rtol=1e-5)
    compiled = not cst.interpret
    ratio = plan.run_bytes_per_superstep(grid_shape, "temporal", compiled) \
        / plan.run_bytes_per_superstep(grid_shape, compiled=compiled)
    print(f"variant={cst.variant}: matches plain at ulp; modeled HBM "
          f"bytes/superstep {ratio:.2f}x of plain  OK")

    # the same handle compiles every execution shape: a batched executable
    # runs B independent grids as ONE donated dispatch
    B = 2
    csb = repro.stencil(program).compile(grid_shape, steps=steps,
                                         plan=plan, batch=B)
    outs = csb.run(jnp.stack([grid, grid]))
    assert outs.shape == (B, *grid_shape)
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(out))
    print(f"batched: {B} grids, one executable, bit-equal to the single "
          f"run  OK")
    print("(multi-device: compile(devices=N) searches mesh decompositions; "
          "see README)")


if __name__ == "__main__":
    main()
