"""repro: TPU-native high-order stencil framework (Zohouri et al., 2020).

One front door::

    import repro

    program = repro.StencilProgram(ndim=2, radius=4)
    cs = repro.stencil(program).compile((4096, 4096), steps=64, plan="auto")
    out = cs.run(grid)

``repro.stencil(program, coeffs=...)`` binds a program to coefficients;
``.compile(...)`` resolves the blocking plan (autotuner + plan cache),
backend, and — for ``devices`` — the mesh decomposition, then hands back a
``CompiledStencil`` that dispatches single-device, batched, sharded, and
pipelined runs through one executor (DESIGN.md §9).  The legacy entry
points (``StencilEngine``, ``kernels.ops.stencil_run``,
``DistributedStencil``) survive as bit-compatible deprecation shims.

``repro.obs`` is the flight recorder: every compile and run is a
``repro.*`` span on the ``jax.profiler`` clock (never blocking, free when
no profiler runs), JAX's compile phases inside them are summed in
``repro.obs.compile_totals()``, and ``with repro.obs.profile() as rec:``
(or ``REPRO_OBS=1``) records the spans' host times and the tuner's
predicted-vs-measured accuracy samples (off by default).
"""

from repro import obs
from repro.backends import (
    available_backends,
    backend_traits,
    default_backend_name,
    lower,
    pipelined_variant,
    register_backend,
)
from repro.core.blocking import BlockPlan, plan_blocking
from repro.core.program import ProgramCoeffs, StencilProgram
from repro.executor import CompiledStencil, Stencil, stencil
from repro.tuning import TunedPlan, autotune

__version__ = "0.3.0"

__all__ = [
    "BlockPlan",
    "CompiledStencil",
    "ProgramCoeffs",
    "Stencil",
    "StencilProgram",
    "TunedPlan",
    "autotune",
    "available_backends",
    "backend_traits",
    "default_backend_name",
    "lower",
    "obs",
    "pipelined_variant",
    "plan_blocking",
    "register_backend",
    "stencil",
    "__version__",
]
