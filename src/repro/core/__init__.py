"""Core library: the paper's high-order stencil technique as composable JAX.

Layers:
  program    — StencilProgram IR: shape/boundary-parametric tap sets
  spec       — legacy radius-parameterized star description (thin alias)
  codegen    — tap-set update builders (the boundary-condition "code generator")
  reference  — naive jnp oracle + independent numpy oracle
  blocking   — spatial+temporal blocking plans, eq. 2 (csize) + VMEM budget
  perf_model — the paper's FPGA performance model, reproduced for validation
  temporal   — superstep driver built on the Pallas kernels
  distributed— shard_map domain decomposition + deep-halo exchange
  compat     — Auto-axis mesh, shard_map and tracer helpers

Backends (``repro.backends``) lower a program+plan to an executable.
"""

from repro.core.blocking import BlockPlan, PlanEstimate, estimate, plan_blocking
from repro.core.program import ProgramCoeffs, StencilProgram
from repro.core.spec import StencilCoeffs, StencilSpec

__all__ = [
    "BlockPlan",
    "PlanEstimate",
    "ProgramCoeffs",
    "StencilCoeffs",
    "StencilProgram",
    "StencilSpec",
    "estimate",
    "plan_blocking",
]
