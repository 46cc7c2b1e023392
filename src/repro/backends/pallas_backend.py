"""Pallas backends: the temporal-blocked superstep kernels behind the registry.

Version 1 targets the installed Pallas API (``pltpu.MemorySpace``); a
future API break becomes a ``version=2`` registration rather than an
edit-in-place, so old lowerings remain addressable.

The ``-pipelined`` siblings select the double-buffered prefetch kernel
(``kernels/common.build_pipelined_kernel``) — the TPU analogue of the
paper's deep pipeline (§III.A), where the DMA for block g+1 is in flight
while block g computes.  Making it a *backend name* (rather than a hidden
flag) puts it on the autotuner's search axis and into the plan-cache key,
so a plan tuned on one kernel variant never silently serves the other.

``run`` on every pallas backend goes through the fused run executor
(``ops._stencil_run(fused=True)``): one donated executable per run, the
remainder superstep folded in.  All backends accept a leading batch axis
(``(B, *grid)``) on both ``superstep`` and ``run``.
"""

from __future__ import annotations

from typing import Optional

from repro.core.blocking import BlockPlan
from repro.core.program import ProgramCoeffs, StencilProgram
from repro.backends.registry import (BackendTraits, LoweredStencil,
                                     register_backend)
from repro.kernels import ops


def _make(program: StencilProgram, plan: Optional[BlockPlan],
          coeffs: ProgramCoeffs, interpret: bool,
          variant: str) -> LoweredStencil:
    if plan is None:
        raise ValueError("pallas backends need a BlockPlan")

    def superstep_fn(grid, c):
        return ops.stencil_superstep(grid, program, c, plan,
                                     interpret=interpret,
                                     variant=variant)

    def run_fn(grid, c, steps):
        return ops._stencil_run(grid, program, c, plan, steps,
                                interpret=interpret, variant=variant)

    return LoweredStencil(program, plan, coeffs, superstep_fn, run_fn)


@register_backend("pallas-tpu", version=1,
                  traits=BackendTraits(local_kernel=True, fused_run=True))
def pallas_tpu(program, plan, coeffs) -> LoweredStencil:
    """Compiled Pallas kernels (requires a TPU backend)."""
    return _make(program, plan, coeffs, interpret=False, variant="plain")


@register_backend("pallas-interpret", version=1,
                  traits=BackendTraits(interpret=True, local_kernel=True,
                                       fused_run=True))
def pallas_interpret(program, plan, coeffs) -> LoweredStencil:
    """Same kernels under the Pallas interpreter — CPU CI / debugging."""
    return _make(program, plan, coeffs, interpret=True, variant="plain")


@register_backend("pallas-tpu-pipelined", version=1,
                  traits=BackendTraits(variant="pipelined", local_kernel=True,
                                       fused_run=True))
def pallas_tpu_pipelined(program, plan, coeffs) -> LoweredStencil:
    """Double-buffered prefetch kernels, compiled mode."""
    return _make(program, plan, coeffs, interpret=False, variant="pipelined")


@register_backend("pallas-interpret-pipelined", version=1,
                  traits=BackendTraits(interpret=True, variant="pipelined",
                                       local_kernel=True, fused_run=True))
def pallas_interpret_pipelined(program, plan, coeffs) -> LoweredStencil:
    """Double-buffered prefetch kernels under the interpreter (CPU CI)."""
    return _make(program, plan, coeffs, interpret=True, variant="pipelined")


# The temporal variant's chunk-deep launch consumes TEMPORAL_CHUNK supersteps
# of halo per window load, which the per-superstep distributed exchange cannot
# feed — so it declares local_kernel=False and the executor refuses it for
# sharded runs with a targeted diagnostic instead of computing garbage halos.

@register_backend("pallas-tpu-temporal", version=1,
                  traits=BackendTraits(variant="temporal", fused_run=True))
def pallas_tpu_temporal(program, plan, coeffs) -> LoweredStencil:
    """Superstep-chunking kernels (TEMPORAL_CHUNK fused supersteps),
    compiled mode."""
    return _make(program, plan, coeffs, interpret=False, variant="temporal")


@register_backend("pallas-interpret-temporal", version=1,
                  traits=BackendTraits(interpret=True, variant="temporal",
                                       fused_run=True))
def pallas_interpret_temporal(program, plan, coeffs) -> LoweredStencil:
    """Superstep-chunking kernels under the interpreter (CPU CI)."""
    return _make(program, plan, coeffs, interpret=True, variant="temporal")
