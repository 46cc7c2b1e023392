"""One front door: ``repro.stencil(program).compile(...)`` — the unified
executor API over every run shape the repo knows.

The paper's whole point is that ONE parameterized design (radius, blocking,
par_time) covers every stencil configuration; this module is that claim at
the API level.  Historically the repo exposed four divergent run surfaces —
``kernels.ops.stencil_run``, ``StencilEngine``, ``DistributedStencil``, and
``StencilServer`` — each with its own plan/backend/batch/steps plumbing and
``tuning.autotune`` bolted on the side.  Now:

    sten = repro.stencil(program, coeffs=...)      # describe once
    cs = sten.compile((4096, 4096), steps=64,      # resolve everything
                      batch=None, devices=None,
                      plan="auto", backend=None,
                      variant=None, donate=True)
    out = cs.run(grid)                             # one dispatch

``compile`` resolves the blocking plan (autotuner + persistent plan cache
for ``plan="auto"``, the pure model planner for ``plan="model"``, or a
caller-pinned ``BlockPlan``), the backend (registry name, its
``-pipelined``/``-temporal`` variant sibling when ``variant=`` asks — the
deprecated ``pipelined=True`` bool still maps to ``variant="pipelined"``),
and — for ``devices`` > 1 — the mesh decomposition
(``enumerate_decompositions`` via the mesh-aware tuner, or model-ranked
against a pinned plan).  The returned :class:`CompiledStencil` carries
``.plan``, ``.decomp``, ``.cost`` (the roofline model's predicted GB/s /
GFLOP/s / bound) and dispatches ``.run`` to exactly one of three internal
executors:

    devices <= 1, pallas backend  -> the fused run executor
                                     (``kernels/common.run_call``: one
                                     donated executable, dynamic superstep
                                     count, remainder folded in)
    devices <= 1, oracle backend  -> the backend's registry lowering
                                     (``xla-reference``: the jnp loop)
    devices  > 1                  -> the sharded fused executor
                                     (``core/distributed``: shard_map +
                                     deep-halo exchange, same donated
                                     one-executable contract on the mesh)

Executable caching is inherited from those executors: any
``steps = k * par_time + rem`` with the same remainder (and the same batch
rank) reuses one compile — ``run_call``'s jit cache on a single device, the
per-instance ``(rem, batch-rank)`` table on the mesh — so repeated
``.run()`` calls and varying step counts are O(1) compiles.

The legacy entry points survive as thin deprecation-warning shims over this
module (bit-compatible; see ``kernels/ops.stencil_run``,
``core/temporal.StencilEngine``, ``core/distributed.DistributedStencil``).
"""

from __future__ import annotations

import math
import operator
import warnings
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro import obs
from repro.analysis.hw import TpuChip, V5E, chip_for_kind
from repro.backends import lower, resolve_backend
from repro.core import compat
from repro.core.blocking import BlockPlan, plan_blocking
from repro.core.distributed import Decomposition, DistributedStencil
from repro.core.program import (ProgramCoeffs, StencilProgram, as_program,
                                normalize_coeffs)
from repro.kernels import common, ops
from repro.lint.diagnostics import DiagnosticError, raise_on_error
from repro.lint.diagnostics import error as _diag
from repro.lint.dataflow import verify_dataflow
from repro.lint.sanitize import sanitize_run
from repro.lint.verify import check as _preflight
from repro.tuning.model_rank import RankedCandidate, predict, rank
from repro.tuning.space import (Candidate, MeshDecomposition,
                                enumerate_decompositions, fits_shard,
                                halo_aligned)

Devices = Union[None, int, Tuple[int, ...]]


def local_chip() -> TpuChip:
    """The planner's target: the attached TPU, looked up by device kind
    (an unknown kind raises), or V5E on a host without a TPU."""
    if jax.default_backend() != "tpu":
        return V5E
    return chip_for_kind(jax.devices()[0].device_kind)


def _as_int(value) -> Optional[int]:
    """``operator.index``'d value (numpy ints included), or None for
    non-integral types — bools deliberately excluded."""
    if isinstance(value, bool):
        return None
    try:
        return operator.index(value)
    except TypeError:
        return None


def _normalize_variant_request(variant: Optional[str],
                               pipelined: Optional[bool]) -> Optional[str]:
    """Apply the deprecated ``pipelined=`` shim to a ``variant=`` request.

    ``pipelined`` left at its ``None`` default means the caller never used
    the legacy spelling — ``variant`` passes through untouched (``None`` =
    resolve the backend name as given, search variants under tuning).
    An explicit bool warns and maps bit-compatibly (True -> "pipelined",
    False -> "plain"); mixing both spellings is an RP114 rejection rather
    than a silent precedence rule.
    """
    if pipelined is None:
        return variant
    if variant is not None:
        raise DiagnosticError([_diag(
            "RP114",
            f"conflicting kernel-variant requests: pipelined={pipelined!r} "
            f"and variant={variant!r} were both given",
            hint="pass only variant= ('plain' | 'pipelined' | 'temporal' | "
                 "'auto'); pipelined= is a deprecated alias for "
                 "variant='pipelined'")])
    warnings.warn(
        "pipelined= is deprecated; pass variant='pipelined' "
        "(or variant='plain') instead", DeprecationWarning, stacklevel=3)
    return "pipelined" if pipelined else "plain"


def _check_steps(steps, context: str = "") -> int:
    """Validate a step count: integral, >= 1 (RP102 on rejection)."""
    v = _as_int(steps)
    if v is None or v < 1:
        raise DiagnosticError([_diag(
            "RP102",
            f"steps must be an int >= 1 (got {steps!r}){context}",
            hint="run at least one time step; fractional or zero step "
                 "counts have no executable")])
    return v


def stencil(program, coeffs=None) -> "Stencil":
    """The front door: bind a program (or legacy spec) to its coefficients.

    Returns a :class:`Stencil` handle whose :meth:`Stencil.compile` resolves
    plan/backend/decomposition and hands back a runnable
    :class:`CompiledStencil`.  ``coeffs`` defaults to the program's
    canonical ``default_coeffs()``; legacy ``StencilCoeffs`` are normalized.
    """
    return Stencil(program, coeffs)


class Stencil:
    """A program + coefficients, ready to compile for any execution shape."""

    def __init__(self, program, coeffs=None):
        self.program: StencilProgram = as_program(program)
        if coeffs is None:
            coeffs = self.program.default_coeffs()
        self.coeffs: ProgramCoeffs = normalize_coeffs(self.program, coeffs)

    def __repr__(self) -> str:
        p = self.program
        return (f"Stencil({p.ndim}D {p.shape} r={p.radius} "
                f"boundary={p.boundary})")

    # -- compile -------------------------------------------------------------

    def compile(self, grid_shape, *, steps: int,
                batch: Optional[int] = None,
                devices: Devices = None,
                plan: Union[str, BlockPlan] = "auto",
                backend: Optional[str] = None,
                variant: Optional[str] = None,
                pipelined: Optional[bool] = None,
                donate: bool = True,
                interpret: Optional[bool] = None,
                hw: Optional[TpuChip] = None,
                max_par_time: int = 32,
                cache: bool = True,
                cache_path: Optional[str] = None,
                sanitize: bool = False) -> "CompiledStencil":
        """Resolve plan, backend, and placement into a runnable executable.

        See :meth:`_compile` for the parameter contract.  The whole
        resolution is the ``repro.compile`` program span; when the flight
        recorder is on (``REPRO_OBS=1`` / ``repro.obs.profile()``) the span
        also records the plan source, plan-cache hit/miss, backend@version,
        decomposition, the model's HBM-traffic prediction and the plan's
        redundant work (``CompiledStencil.model_compute_redundancy``), the
        rows one strip of the kernel computes
        (``CompiledStencil.kernel_strip_rows``), and on a mesh the bytes
        one chip sends per exchange
        (``CompiledStencil.exchange_bytes_per_superstep``) and the
        exchanges a call makes, one a superstep.
        Nothing is compiled here: XLA compiles at the first ``run``.
        """
        variant = _normalize_variant_request(variant, pipelined)
        kwargs = dict(steps=steps, batch=batch, devices=devices, plan=plan,
                      backend=backend, variant=variant, donate=donate,
                      interpret=interpret, hw=hw, max_par_time=max_par_time,
                      cache=cache, cache_path=cache_path, sanitize=sanitize)
        rec = obs.active()
        plan_source = plan if isinstance(plan, str) else "pinned"
        before = common.trace_counts() if rec is not None else None
        with obs.span("compile", plan_source=plan_source) as sp:
            cs = self._compile(grid_shape, **kwargs)
            if rec is not None:
                supersteps = -(-cs.steps // cs.plan.par_time)
                sp.set(**cs._span_attrs())
                sp.set(cache_hit=cs.from_plan_cache,
                       supersteps=supersteps,
                       model_bytes_per_superstep=cs.plan
                       .run_bytes_per_superstep(cs.grid_shape, cs.variant,
                                                cs.cost.candidate.compiled),
                       model_compute_redundancy=cs.model_compute_redundancy,
                       kernel_strip_rows=cs.kernel_strip_rows,
                       trace_delta=_trace_delta(before) or None)
                if cs.exchange_bytes_per_superstep is not None:
                    sp.set(exchange_bytes_per_superstep=cs
                           .exchange_bytes_per_superstep,
                           exchanges_per_call=supersteps)
                rec.count("compile.plan_cache_hit" if cs.from_plan_cache
                          else "compile.plan_cache_miss")
        return cs

    def _compile(self, grid_shape, *, steps: int,
                 batch: Optional[int] = None,
                 devices: Devices = None,
                 plan: Union[str, BlockPlan] = "auto",
                 backend: Optional[str] = None,
                 variant: Optional[str] = None,
                 donate: bool = True,
                 interpret: Optional[bool] = None,
                 hw: Optional[TpuChip] = None,
                 max_par_time: int = 32,
                 cache: bool = True,
                 cache_path: Optional[str] = None,
                 sanitize: bool = False) -> "CompiledStencil":
        """Resolve plan, backend, and placement into a runnable executable.

        grid_shape   spatial extent of one grid (must match the program's
                     rank); ``batch`` adds a leading ``(B, *grid)`` axis of
                     independent grids.
        steps        the step count the executable is built for; ``run``
                     may override it per call (same-remainder counts reuse
                     the same compile).  Must be >= 1.
        devices      None/1 = single device; an int N searches every
                     factorization of N over the grid axes (mesh-aware
                     tuner); a tuple pins shards-per-axis explicitly.
        plan         "auto"  — the autotuner (model-guided, persistent plan
                               cache; ``cache``/``cache_path`` control it),
                     "model" — the zero-state model planner
                               (``blocking.plan_blocking``), or
                     a ``BlockPlan`` pinned by the caller.
        backend      a registry backend name (default: the platform's
                     pallas backend).
        variant      which kernel lowering of the backend family to use:
                     "plain", "pipelined" (double-buffered prefetch), or
                     "temporal" (superstep-chunked in-VMEM fusion) resolve
                     the matching registry sibling; "auto" (and the None
                     default) lets ``plan="auto"`` search every registered
                     variant of the backend and keeps the model's winner.
                     Outside tuning, None/"auto" mean the backend name as
                     given (i.e. plain unless the name itself pins a
                     variant).  The deprecated ``pipelined=`` bool maps
                     onto this (True -> "pipelined", False -> "plain");
                     passing both is an RP114 rejection.
        donate       donate the caller's (sharded) buffer to the run on the
                     mesh path — supersteps then update it in place and the
                     input is consumed.  On a single device the fused
                     executor donates only its internal padded carry, so
                     the caller's grid is never consumed either way.
        interpret    force the Pallas interpreter on/off (None = follow the
                     backend's traits / platform auto-detection).
        hw           the chip the planner targets (None = the local TPU's
                     entry in ``analysis.hw.CHIPS``, V5E on a host without
                     one).
        sanitize     also run the RP4xx canary sanitizer (interpret-mode
                     execution with NaN-poisoned halos, ``repro.lint.
                     sanitize``) before accepting the compile — slow but
                     the definitive wrong-result debugger; the symbolic
                     dataflow verifier always runs.  The report survives
                     on ``CompiledStencil.sanitize_report``.  Sharded
                     compiles skip the canary run (their exchange strips
                     are covered by the symbolic half).
        """
        prog = self.program
        if hw is None:
            hw = local_chip()
        try:
            # operator.index: accept ints/np ints, reject silently-truncating
            # floats — a (128.5, 512) grid must fail HERE, not at run()
            grid_shape = tuple(operator.index(s) for s in grid_shape)
        except TypeError:
            raise DiagnosticError([_diag(
                "RP101",
                f"grid_shape must be a sequence of ints (got {grid_shape!r})",
                hint="pass the spatial extents, e.g. (4096, 4096)")])
        if len(grid_shape) != prog.ndim or any(s < 1 for s in grid_shape):
            raise DiagnosticError([_diag(
                "RP101",
                f"grid_shape {grid_shape} does not describe a {prog.ndim}-D "
                f"grid for this {prog.ndim}-D program (expected "
                f"{prog.ndim} positive extents); a leading batch axis is "
                f"declared via compile(batch=B), not in grid_shape",
                hint=f"give exactly {prog.ndim} positive extents")])
        steps = _check_steps(
            steps,
            "; compile() pins the step count the executable is built for, "
            "and run(grid, steps=n) may override it per call")
        if batch is not None:
            b = _as_int(batch)
            if b is None or b < 1:
                raise DiagnosticError([_diag(
                    "RP103",
                    f"batch must be None (unbatched) or an int >= 1 — the "
                    f"extent of the leading (B, *grid) axis of independent "
                    f"grids (got {batch!r})",
                    hint="drop batch= for a single grid, or stack "
                         "independent grids along a leading axis")])
            batch = b

        decomp_axes, n_devices = _normalize_devices(prog, devices)

        concrete = None if variant in (None, "auto") else variant
        name, version, traits = resolve_backend(backend, variant=concrete)
        # search the variant axis only when nothing pinned one: an explicit
        # variant= request resolved above, and an explicit -pipelined/
        # -temporal backend name must stay exactly what the caller named
        variant_search = (plan == "auto" and concrete is None
                          and traits.variant == "plain")
        if n_devices > 1 and traits.variant == "temporal":
            raise DiagnosticError([_diag(
                "RP110",
                f"backend {name!r} (the temporally-fused variant) cannot "
                f"run sharded: its launch advances TEMPORAL_CHUNK "
                f"supersteps per kernel, but the mesh executor exchanges "
                f"halos once per superstep — the chunk would read "
                f"neighbor cells that were never exchanged; "
                f"compile(devices={devices!r}) needs a per-superstep "
                f"local kernel",
                hint="drop devices= for the temporal variant, or use "
                     "variant='plain'/'pipelined' on the mesh")])
        if n_devices > 1 and not traits.local_kernel:
            raise DiagnosticError([_diag(
                "RP110",
                f"backend {name!r} cannot run sharded (it declares no "
                f"local_kernel trait — its lowering pads its own "
                f"boundaries and cannot consume an exchanged halo); "
                f"compile(devices={devices!r}) needs a pallas backend",
                hint="drop devices= for this backend, or use a pallas "
                     "backend for mesh runs")])
        if n_devices > len(jax.devices()):
            raise DiagnosticError([_diag(
                "RP110",
                f"compile(devices={devices!r}) needs {n_devices} visible "
                f"devices but jax sees {len(jax.devices())}; on a CPU host "
                f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n_devices} before importing jax",
                hint="request at most the visible device count")])

        tuned = None
        if isinstance(plan, BlockPlan):
            resolved = plan
            if n_devices > 1 and decomp_axes is None:
                decomp_axes = _pick_decomposition(
                    prog, resolved, grid_shape, n_devices, hw, name, version)
        elif plan == "auto":
            from repro.tuning import autotune
            tuned = autotune(
                prog, hw, grid_shape=grid_shape, backend=name,
                variant="auto" if variant_search else None,
                measure=False, cache=cache, cache_path=cache_path,
                max_par_time=max_par_time,
                n_devices=n_devices if (n_devices > 1
                                        and decomp_axes is None) else None,
                decomposition=decomp_axes if n_devices > 1 else None)
            resolved = tuned.plan
            if tuned.backend != name:
                # the variant search picked a sibling lowering of the family
                name, version, traits = resolve_backend(tuned.backend)
            if n_devices > 1:
                decomp_axes = tuned.decomp or decomp_axes
        elif plan == "model":
            resolved = plan_blocking(prog, hw, grid_shape=grid_shape,
                                     max_par_time=max_par_time,
                                     variant=traits.variant,
                                     compiled=traits.compiled).plan
            if n_devices > 1 and decomp_axes is None:
                decomp_axes = _pick_decomposition(
                    prog, resolved, grid_shape, n_devices, hw, name, version)
        else:
            raise DiagnosticError([_diag(
                "RP112",
                f'plan must be "auto", "model", or a BlockPlan '
                f"(got {plan!r})",
                hint='use plan="auto" unless pinning a tuned BlockPlan')])

        if n_devices <= 1:
            decomp_axes = None
        # fail-fast pre-flight: every tuner legality constraint re-checked
        # statically (eq. 2 csize, the VMEM budget, per-shard halo bounds,
        # dtype support) BEFORE any Pallas lowering — raises DiagnosticError
        # with stable RP codes; warnings survive on CompiledStencil.preflight
        preflight = _preflight(prog, resolved, grid_shape, hw,
                               decomp=decomp_axes, variant=traits.variant)
        if interpret is None and traits.fused_run:
            # pin the backend's declared mode BEFORE any executor is built
            # (the mesh executor would otherwise auto-resolve None): a
            # compiled backend (pallas-tpu, interpret=False) must FAIL on
            # a host that cannot compile it — exactly like its registry
            # lowering — not silently fall back to the interpreter
            interpret = traits.interpret
        sanitize_report = None
        if traits.fused_run:
            # RP4xx: prove the padded ring schedule itself (wrap/exchange
            # copy depths, ping-pong aliasing, per-superstep coverage) —
            # pure numpy, well under the 2ms pre-flight budget.  The
            # sanitizer is the opt-in dynamic oracle on top.
            preflight.extend(raise_on_error(
                verify_dataflow(prog, resolved, grid_shape, steps=steps,
                                variant=traits.variant, decomp=decomp_axes,
                                compiled=not interpret),
                source="dataflow"))
            if sanitize and decomp_axes is None:
                sanitize_report = sanitize_run(
                    prog, resolved, grid_shape, steps=steps,
                    coeffs=self.coeffs, variant=traits.variant)
                raise_on_error(sanitize_report.diagnostics,
                               source="sanitize")
        cand = Candidate(
            plan=resolved, backend=name, backend_version=version,
            halo_aligned=halo_aligned(resolved.par_time, prog.halo_radius),
            variant=traits.variant,
            decomp=MeshDecomposition(decomp_axes) if decomp_axes else None)
        cost = predict(prog, cand, hw, grid_shape=grid_shape)

        dist = None
        lowered = None
        if decomp_axes is not None:
            names = tuple(f"d{i}" for i in range(prog.ndim))
            mesh = compat.make_mesh(decomp_axes, names)
            decomp = Decomposition(tuple(
                (names[i],) if decomp_axes[i] > 1 else ()
                for i in range(prog.ndim)))
            dist = DistributedStencil(
                prog, self.coeffs, resolved, mesh, decomp, grid_shape,
                interpret=interpret, backend=name, _warn=False)
        elif not traits.fused_run:
            # a backend whose run is NOT the fused executor (the oracle, or
            # a third-party lowering) executes through its own registry
            # lowering — the fast path below would silently bypass it
            lowered = lower(prog, resolved, coeffs=self.coeffs, backend=name)

        return CompiledStencil(
            program=prog, coeffs=self.coeffs, grid_shape=grid_shape,
            steps=steps, batch=batch, plan=resolved, backend=name,
            backend_version=version, decomp=decomp_axes, cost=cost,
            tuned=tuned, variant=traits.variant, donate=donate,
            interpret=interpret, devices=n_devices, dist=dist,
            lowered=lowered, hw=hw, preflight=preflight,
            sanitize_report=sanitize_report)


#: back-compat alias — the counter diff now lives with the counters.
_trace_delta = common.trace_delta


def _normalize_devices(prog: StencilProgram, devices: Devices):
    """-> (explicit shards-per-axis or None, total device count)."""
    if devices is None:
        return None, 1
    n = _as_int(devices)
    if n is not None:
        if n < 1:
            raise DiagnosticError([_diag(
                "RP110", f"devices must be >= 1 (got {devices})",
                hint="pass a positive device count or drop devices=")])
        return None, n
    try:
        axes = tuple(operator.index(s) for s in devices)
    except TypeError:
        raise DiagnosticError([_diag(
            "RP110",
            f"devices must be None, an int device count, or a "
            f"{prog.ndim}-tuple of shards per grid axis (got {devices!r})",
            hint="an int searches every factorization; a tuple pins "
                 "shards per axis")])
    if len(axes) != prog.ndim or any(s < 1 for s in axes):
        raise DiagnosticError([_diag(
            "RP110",
            f"devices {devices!r} must give one positive shard count per "
            f"grid axis ({prog.ndim} of them)",
            hint=f"give {prog.ndim} positive shard counts")])
    return axes, math.prod(axes)


def _pick_decomposition(program, plan: BlockPlan, grid_shape, n_devices: int,
                        hw: TpuChip, backend: str,
                        version: int) -> Tuple[int, ...]:
    """Best feasible split of ``n_devices`` for a caller-pinned plan.

    The plan is fixed, so only the decomposition axis is searched: every
    factorization that divides the grid and satisfies the per-shard eq. 2
    constraints, ranked by the aggregate mesh model (exchange charged).
    """
    feasible = [dc for dc in
                enumerate_decompositions(program.ndim, n_devices, grid_shape)
                if fits_shard(plan, dc, grid_shape)]
    if not feasible:
        raise DiagnosticError([_diag(
            "RP107",
            f"no feasible decomposition of {n_devices} devices over grid "
            f"{grid_shape} for block={plan.block_shape} "
            f"par_time={plan.par_time} (every split must divide the grid, "
            f"tile the local extent by the block, and keep the halo "
            f"shallower than the shard)",
            hint="pass devices=<shards per axis> or let plan='auto' "
                 "search blocking and split together")])
    aligned = halo_aligned(plan.par_time, program.halo_radius)
    cands = [Candidate(plan=plan, backend=backend, backend_version=version,
                       halo_aligned=aligned, decomp=dc) for dc in feasible]
    best = rank(program, cands, hw, grid_shape=grid_shape)[0]
    return best.candidate.decomp.axis_shards


class CompiledStencil:
    """A resolved, runnable stencil executable.

    ``plan``/``backend``/``decomp``/``cost`` expose what ``compile``
    resolved; ``run`` dispatches to the matching internal executor.  One
    ``CompiledStencil`` owns at most one sharded executor instance, so its
    per-(remainder, batch-rank) executable table is reused across ``run``
    calls; the single-device path shares the process-wide ``run_call`` jit
    cache.
    """

    def __init__(self, *, program: StencilProgram, coeffs: ProgramCoeffs,
                 grid_shape: Tuple[int, ...], steps: int,
                 batch: Optional[int], plan: BlockPlan, backend: str,
                 backend_version: int, decomp: Optional[Tuple[int, ...]],
                 cost: RankedCandidate, tuned, variant: str, donate: bool,
                 interpret: Optional[bool], devices: int,
                 dist: Optional[DistributedStencil], lowered,
                 hw: TpuChip = V5E, preflight=None, sanitize_report=None):
        #: non-fatal pre-flight diagnostics (RP106 alignment, RP108
        #: wrap-degenerate, RP113 overlap tax) the verifier attached at
        #: compile time — errors never get here, they raise.
        self.preflight = list(preflight or [])
        #: the RP4xx canary report when compiled with ``sanitize=True``
        #: (None otherwise); its errors raise at compile, so a stored
        #: report is always clean.
        self.sanitize_report = sanitize_report
        self.program = program
        self.hw = hw
        self.coeffs = coeffs
        self.grid_shape = grid_shape
        self.steps = steps
        self.batch = batch
        self.plan = plan
        self.backend = backend
        self.backend_version = backend_version
        self.decomp = decomp
        self.cost = cost
        self.tuned = tuned
        #: which kernel lowering compile() resolved ("plain" | "pipelined"
        #: | "temporal"); ``pipelined`` stays as the deprecated bool view.
        self.variant = variant
        self.pipelined = variant == "pipelined"
        self.donate = donate
        self.interpret = interpret
        self.devices = devices
        self._dist = dist
        self._lowered = lowered
        # The xla-reference oracle has no internal jit entry of its own, so
        # the executor supplies one — otherwise every .run() would
        # re-execute the eager reference loop (static steps: its fori_loop
        # bounds are python ints).  Third-party lowerings run as they are;
        # whether/what to jit is their own contract.
        if lowered is None:
            self._lowered_jit = None
        elif backend == "xla-reference":
            self._lowered_jit = jax.jit(lambda g, s: lowered.run(g, s),
                                        static_argnums=1)
        else:
            self._lowered_jit = lowered.run

    @property
    def model_compute_redundancy(self) -> float:
        """Cell-updates the kernel computes per superstep over the useful
        ones — the frames of every launch, the grid's round-up to whole
        blocks included (``BlockPlan.compute_redundancy``); 1.0 is no
        redundant work.  Charged like ``cost``, by the backend's kernel;
        on a mesh the blocks tile every shard exactly, so the whole grid
        gives the same ratio."""
        return self.plan.compute_redundancy(
            self.grid_shape, self.variant, self.cost.candidate.compiled)

    @property
    def kernel_strip_rows(self) -> Optional[int]:
        """Rows one strip-loop iteration of the padded-carry kernel
        computes: :func:`repro.kernels.common.strip_rows` of the frame
        every launch of the run sweeps (block plus ring, the ring as
        ``ring_schedule`` lays it out).  8, one row tile, is a frame too
        wide for taller strips.  None when the run takes no such kernel
        (a registry lowering, or the wrap-degenerate re-pad fallback)."""
        if self._lowered is not None:
            return None
        sched = common.ring_schedule(
            self.program, self.plan, self.grid_shape, self.steps,
            variant=self.variant, decomp=self.decomp,
            compiled=not self.interpret)
        if sched.fallback:
            return None
        return common.strip_rows(tuple(
            b + 2 * g
            for b, g in zip(self.plan.block_shape, sched.layout.ring)))

    @property
    def exchange_bytes_per_superstep(self) -> Optional[int]:
        """Bytes one chip sends over ICI per superstep of a mesh run (the
        halo strips ``core/distributed._exchange_into_ring`` permutes,
        counted from its padded geometry); None on one device."""
        if self._dist is None:
            return None
        return self._dist.exchange_bytes_per_superstep(self.batch)

    @property
    def from_plan_cache(self) -> bool:
        """True when ``plan="auto"`` was served by the persistent cache."""
        return bool(self.tuned is not None and self.tuned.from_cache)

    def __repr__(self) -> str:
        where = "1 device" if self.decomp is None else \
            f"mesh {'x'.join(map(str, self.decomp))}"
        b = "" if self.batch is None else f" batch={self.batch}"
        v = "" if self.variant == "plain" else f" variant={self.variant}"
        return (f"CompiledStencil(grid={self.grid_shape}{b} "
                f"steps={self.steps} block={self.plan.block_shape} "
                f"par_time={self.plan.par_time} backend={self.backend}"
                f"{v} on {where})")

    # -- execution -----------------------------------------------------------

    def _check_grid(self, grid) -> None:
        want = self.grid_shape if self.batch is None \
            else (self.batch,) + self.grid_shape
        if tuple(grid.shape) == want:
            return
        spatial = len(self.grid_shape)
        if self.batch is None and grid.ndim == spatial + 1 \
                and tuple(grid.shape[1:]) == self.grid_shape:
            raise DiagnosticError([_diag(
                "RP103",
                f"this executable was compiled unbatched for grid "
                f"{self.grid_shape} but got a batched grid of shape "
                f"{tuple(grid.shape)}; compile(batch={grid.shape[0]}) to "
                f"run a leading axis of independent grids",
                hint=f"recompile with batch={grid.shape[0]}")])
        if self.batch is not None and tuple(grid.shape) == self.grid_shape:
            raise DiagnosticError([_diag(
                "RP103",
                f"this executable was compiled for batch={self.batch} "
                f"grids of shape {self.grid_shape} but got a single "
                f"unbatched grid {tuple(grid.shape)}; stack the grids "
                f"(B, *grid) or compile(batch=None)",
                hint="batch rank is pinned at compile time")])
        raise DiagnosticError([_diag(
            "RP101",
            f"grid shape {tuple(grid.shape)} does not match the compiled "
            f"{'batch=' + str(self.batch) + ' ' if self.batch else ''}"
            f"grid_shape {want}; compile() pins shapes so the executable "
            f"cache stays exact — recompile for a different shape",
            hint=f"recompile for grid {tuple(grid.shape)}")])

    def run(self, grid, steps: Optional[int] = None):
        """Advance ``steps`` time steps (default: the compiled count).

        Any ``steps = k * par_time + rem`` with the remainder of an earlier
        call reuses that call's executable; only a new remainder (or batch
        rank) compiles again.  The call returns once its work is enqueued.

        Each call is the ``repro.run`` program span — validation, the
        caller copy (``repro.copy``) and the executor's launch
        (``repro.launch``) — on the profiler's clock; with the flight
        recorder on it also records the host time of that dispatch.  No
        span opens inside a JAX trace (a jitted wrapper around ``run``).
        """
        if compat.tracing(grid):
            span = obs.NULL_SPAN
        else:
            rec = obs.active()
            span = obs.span("run") if rec is None \
                else rec.span("run", **self._span_attrs())
        with span:
            steps = self.steps if steps is None else _check_steps(steps)
            grid = jnp.asarray(grid)
            self._check_grid(grid)
            return self._dispatch(grid, steps)

    def _dispatch(self, grid, steps: int):
        """Route one validated run to the matching internal executor."""
        if self._dist is not None:
            nb = 0 if self.batch is None else 1
            with compat.span("copy", grid):
                g = jax.device_put(grid, self._dist.sharding(nb=nb))
                if not self.donate and g is grid:
                    # device_put was a no-op (already committed with the
                    # target sharding): donation would consume the caller's
                    # buffer, so pay a copy; a fresh device_put result
                    # needs none
                    g = jnp.copy(g)
            return self._dist.run(g, steps)
        if self._lowered is not None:
            with compat.span("launch", grid):
                return self._lowered_jit(grid, steps)
        return ops._stencil_run(grid, self.program, self.coeffs, self.plan,
                                steps, interpret=self.interpret,
                                variant=self.variant)

    # -- telemetry -----------------------------------------------------------

    def _span_attrs(self) -> dict:
        return {
            "backend": f"{self.backend}@{self.backend_version}",
            "grid_shape": list(self.grid_shape),
            "batch": self.batch,
            "devices": self.devices,
            "decomp": None if self.decomp is None else list(self.decomp),
            "block_shape": list(self.plan.block_shape),
            "par_time": self.plan.par_time,
            "variant": self.variant,
            "pipelined": self.pipelined,
            "predicted_gbps": self.cost.predicted_gbps,
            "bound": self.cost.bound,
        }
