"""Public jit'd entry points for the stencil kernels.

``stencil_superstep`` dispatches on program ndim; ``stencil_run`` advances an
arbitrary number of time steps through the *fused run executor*
(``kernels/common.run_call``): one donated, compiled executable that loops
``steps // par_time`` full supersteps with a dynamic trip count and folds the
``steps % par_time`` remainder superstep into the same executable — O(1)
dispatches per run and at most one compile per distinct remainder, instead of
the historical one-dispatch-per-superstep Python chain (kept reachable as
``fused=False`` for A/B testing).

Both entry points accept a leading batch axis — ``(B, *grid)`` runs B
independent grids through one kernel launch (an extra leading pallas grid
dimension) — and a ``variant`` knob ("plain" | "pipelined" | "temporal")
selecting the kernel variant: double-buffered prefetch (the paper's deep
pipeline, §III.A) or superstep chunking (``TEMPORAL_CHUNK`` supersteps fused
per launch).  The deprecated ``pipelined=True`` bool maps to
``variant="pipelined"``.

Both accept the legacy (``StencilSpec``, ``StencilCoeffs``) pair or the
unified-IR (``StencilProgram``, ``ProgramCoeffs``) pair.

``stencil_run`` is a deprecation-warning shim since the unified executor
API landed — ``repro.stencil(program).compile(...).run(grid)`` is the front
door; internal callers (the pallas backends, the executor) use
``_stencil_run`` directly, so the shim costs users nothing but the warning.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax.numpy as jnp

from repro.core import compat
from repro.core.blocking import BlockPlan, normalize_variant
from repro.core.program import as_program, normalize_coeffs
from repro.kernels import common
from repro.kernels.stencil2d import stencil2d_superstep
from repro.kernels.stencil3d import stencil3d_superstep


def stencil_superstep(grid, spec, coeffs, plan: BlockPlan, *,
                      interpret: Optional[bool] = None,
                      pipelined: bool = False,
                      variant: Optional[str] = None):
    # A single superstep cannot amortize a chunk, so the temporal variant's
    # superstep IS the plain kernel (one launch, par_time fused steps).
    v = normalize_variant(variant, pipelined)
    if v == "temporal":
        v = "plain"
    if as_program(spec).ndim == 2:
        return stencil2d_superstep(grid, spec, coeffs, plan,
                                   interpret=interpret, variant=v)
    return stencil3d_superstep(grid, spec, coeffs, plan, interpret=interpret,
                               variant=v)


def stencil_run(grid, spec, coeffs, plan: BlockPlan, steps: int, *,
                interpret: Optional[bool] = None,
                pipelined: bool = False,
                variant: Optional[str] = None,
                fused: bool = True):
    """Deprecated front end of :func:`_stencil_run`.

    Use ``repro.stencil(program, coeffs=...).compile(grid_shape,
    steps=...).run(grid)`` — the unified executor resolves plan/backend/
    placement once and dispatches to the identical fused executor, so the
    shim is bit-compatible.
    """
    warnings.warn(
        "kernels.ops.stencil_run is deprecated; use "
        "repro.stencil(program, coeffs=...).compile(grid_shape, "
        "steps=...).run(grid) (DESIGN.md §9)",
        DeprecationWarning, stacklevel=2)
    return _stencil_run(grid, spec, coeffs, plan, steps,
                        interpret=interpret,
                        pipelined=pipelined,  # legacy-ok
                        variant=variant, fused=fused)


def _stencil_run(grid, spec, coeffs, plan: BlockPlan, steps: int, *,
                 interpret: Optional[bool] = None,
                 pipelined: bool = False,
                 variant: Optional[str] = None,
                 fused: bool = True):
    """Advance ``steps`` time steps using temporal blocking.

    steps = k * period + rem, where period is ``par_time`` (one superstep
    per kernel launch) or, under ``variant="temporal"``,
    ``par_time * TEMPORAL_CHUNK`` (one superstep-chunk per launch): k full
    launches, then a remainder superstep with par_time = rem (same spatial
    blocks, shallower halo).  ``fused=True`` (the default) executes the
    whole run as one donated executable with a dynamic full-launch count
    (see ``common.run_call``); ``fused=False`` keeps the eager Python chain
    of per-launch dispatches.  ``grid`` may carry a leading batch axis of
    independent grids.
    """
    if steps < 0:
        raise ValueError("steps must be >= 0")
    v = normalize_variant(variant, pipelined)
    program = as_program(spec)
    nb = common.batch_dims(program, grid.ndim)
    if steps == 0:
        return grid

    period = plan.par_time * (common.TEMPORAL_CHUNK if v == "temporal"
                              else 1)
    full, rem = divmod(steps, period)
    if not fused:
        # Eager chain: for temporal, each "launch" is the chunk-deep plan
        # through the plain superstep kernel — same math, one dispatch per
        # chunk (the A/B baseline for the fused path).
        step_plan = plan if v != "temporal" else dataclasses.replace(
            plan, par_time=period)
        step_v = "plain" if v == "temporal" else v
        for _ in range(full):
            grid = stencil_superstep(grid, spec, coeffs, step_plan,
                                     interpret=interpret, variant=step_v)
        if rem:
            rem_plan = dataclasses.replace(plan, par_time=rem)
            grid = stencil_superstep(grid, spec, coeffs, rem_plan,
                                     interpret=interpret, variant=step_v)
        return grid

    pc = normalize_coeffs(program, coeffs)
    if interpret is None:
        interpret = common.default_interpret()
    true_shape = grid.shape[nb:]
    # The executor donates its first argument (the carry lives in padded
    # layout internally, pad-once-on-entry / slice-once-on-exit); copy so
    # the caller's buffer is never consumed.
    with compat.span("copy", grid):
        carry = jnp.copy(grid)
    with compat.span("launch", grid):
        return common.run_call(carry, pc.center, pc.taps, full,
                               program=program, plan=plan,
                               true_shape=true_shape, interpret=interpret,
                               rem=rem, variant=v)
