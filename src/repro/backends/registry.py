"""Versioned stencil-backend registry behind a single ``lower()`` entry point.

The frontend (``StencilProgram``) describes *what* to compute; a backend
decides *how*.  This mirrors the layered lowering the paper's toolchain
implies (OpenCL source -> AOC -> bitstream) and that Stencil-HMLS makes
explicit (DSL -> MLIR dialects -> target): the IR stays fixed while backends
evolve independently — and carry a version so a changed lowering can be
introduced as a new version without deleting the old one.

Built-in backends (registered in ``repro.backends``):

* ``pallas-tpu``       — temporal-blocked Pallas kernels, compiled mode.
* ``pallas-interpret`` — same kernels under the Pallas interpreter (CPU CI).
* ``pallas-tpu-pipelined`` / ``pallas-interpret-pipelined``
                       — double-buffered prefetch variant (the paper's deep
                         pipeline); a first-class backend name so the
                         autotuner searches it and the plan cache keys on it.
* ``pallas-tpu-temporal`` / ``pallas-interpret-temporal``
                       — superstep-chunking variant: ``TEMPORAL_CHUNK``
                         supersteps fused per kernel launch over a chunk-deep
                         halo ring, amortizing the carry ping-pong and the
                         window stream (the paper's in-fabric temporal
                         blocking, §III.A).
* ``xla-reference``    — naive jnp step loop through XLA; the semantic
                         oracle, also the fallback when Pallas is unavailable.

Usage::

    program = StencilProgram(ndim=2, radius=3, shape="box",
                             boundary="periodic")
    lowered = lower(program, plan)           # best default backend
    out = lowered.run(grid, steps=12)
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

from repro.core.blocking import BlockPlan, plan_blocking
from repro.core.program import (ProgramCoeffs, StencilProgram, as_program,
                                normalize_coeffs)


@dataclasses.dataclass(frozen=True)
class BackendTraits:
    """Capability flags a backend declares at registration time.

    ``interpret``/``variant`` describe which Pallas kernel configuration
    the backend's lowering selects — ``variant`` is one of
    ``repro.core.blocking.VARIANTS`` ("plain" | "pipelined" | "temporal"),
    with ``pipelined`` kept as the deprecated bool mirror of
    ``variant == "pipelined"``.  ``local_kernel=True`` means the
    backend's superstep can serve as the *local* kernel of the distributed
    stack (``core/distributed.py`` runs it on each shard's halo-exchanged
    block inside ``shard_map``).  The oracle backend lowers a whole-grid
    jnp loop with its own boundary padding, so it cannot — its halos would
    be synthesized locally instead of exchanged.  The temporal variant
    cannot either, for a different reason: its chunk-deep launch would need
    ``TEMPORAL_CHUNK`` supersteps worth of halo exchanged at once.

    ``fused_run=True`` declares that the backend's ``run`` *is* the fused
    run executor (``kernels/ops._stencil_run`` configured by the
    interpret/variant flags above): the unified executor
    (``repro.executor``) then dispatches to it directly — honoring a
    caller ``interpret`` override — instead of through the lowering
    object.  Backends with their own run implementation must leave it
    False or the executor would silently bypass them.
    """

    interpret: bool = False
    pipelined: bool = False
    local_kernel: bool = False
    fused_run: bool = False
    variant: str = "plain"

    @property
    def compiled(self) -> bool:
        """True when runs go through the compiled padded-carry kernel,
        whose frames carry a tile-rounded ring (what the planner charges,
        ``BlockPlan.cells_per_block``)."""
        return self.fused_run and not self.interpret

    def __post_init__(self):
        # Keep the deprecated bool and the variant axis coherent no matter
        # which spelling a registration used.
        if self.pipelined and self.variant == "plain":
            object.__setattr__(self, "variant", "pipelined")
        elif self.variant == "pipelined" and not self.pipelined:
            object.__setattr__(self, "pipelined", True)


class LoweredStencil:
    """A program bound to a backend: ``superstep``/``run`` execute it.

    ``backend_name``/``backend_version`` are stamped by :func:`lower` from
    the registry entry that produced this object — factories need not (and
    should not) hardcode them.
    """

    def __init__(self, program: StencilProgram, plan: Optional[BlockPlan],
                 coeffs: ProgramCoeffs, superstep_fn, run_fn,
                 backend_name: Optional[str] = None,
                 backend_version: Optional[int] = None):
        self.program = program
        self.plan = plan
        self.coeffs = coeffs
        self._superstep_fn = superstep_fn
        self._run_fn = run_fn
        self.backend_name = backend_name
        self.backend_version = backend_version

    def superstep(self, grid, coeffs=None):
        """Advance ``plan.par_time`` steps (1 for plan-less backends)."""
        c = self.coeffs if coeffs is None else \
            normalize_coeffs(self.program, coeffs)
        return self._superstep_fn(grid, c)

    def run(self, grid, steps: int, coeffs=None):
        """Advance an arbitrary number of time steps."""
        c = self.coeffs if coeffs is None else \
            normalize_coeffs(self.program, coeffs)
        return self._run_fn(grid, c, steps)


#: factory(program, plan, coeffs) -> LoweredStencil
BackendFactory = Callable[[StencilProgram, Optional[BlockPlan],
                           ProgramCoeffs], LoweredStencil]

_REGISTRY: Dict[str, Dict[int, BackendFactory]] = {}
_TRAITS: Dict[tuple, BackendTraits] = {}     # (name, version) -> traits


def register_backend(name: str, version: int = 1,
                     traits: Optional[BackendTraits] = None):
    """Decorator registering a backend factory under (name, version).

    ``traits`` declares this version's capabilities (see
    :class:`BackendTraits`); omitted traits default to the most conservative
    flags, so a lowering that never declares ``local_kernel`` can never be
    picked up by the distributed executor — a new version must re-declare
    its capabilities, they do not inherit from older registrations.
    """

    def deco(factory: BackendFactory) -> BackendFactory:
        _REGISTRY.setdefault(name, {})
        if version in _REGISTRY[name]:
            raise ValueError(f"backend {name!r} v{version} already registered")
        _REGISTRY[name][version] = factory
        if traits is not None:
            _TRAITS[(name, version)] = traits
        return factory

    return deco


def backend_traits(name: str,
                   version: Optional[int] = None) -> BackendTraits:
    """The declared :class:`BackendTraits` of a registered backend version
    (highest version when unspecified — :func:`get_backend`'s resolution
    rule, which also supplies the unknown-name/version errors)."""
    _, v = get_backend(name, version)
    return _TRAITS.get((name, v), BackendTraits())


def available_backends() -> Dict[str, tuple]:
    """name -> sorted tuple of registered versions."""
    return {n: tuple(sorted(v)) for n, v in _REGISTRY.items()}


def get_backend(name: str,
                version: Optional[int] = None) -> "tuple[BackendFactory, int]":
    """Resolve (factory, version); highest version wins when unspecified."""
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown backend {name!r}; available: {sorted(_REGISTRY)}")
    versions = _REGISTRY[name]
    v = max(versions) if version is None else version
    if v not in versions:
        raise KeyError(f"backend {name!r} has no version {v}; "
                       f"available: {sorted(versions)}")
    return versions[v], v


def default_backend_name() -> str:
    import jax
    return "pallas-tpu" if jax.default_backend() == "tpu" \
        else "pallas-interpret"


#: Known kernel-variant name suffixes (see ``repro.core.blocking.VARIANTS``).
_VARIANT_SUFFIXES = ("-pipelined", "-temporal")


def _base_name(name: str) -> str:
    """Strip a known variant suffix off a backend name."""
    for suf in _VARIANT_SUFFIXES:
        if name.endswith(suf):
            return name[:-len(suf)]
    return name


def variant_of(name: str, variant: str) -> Optional[str]:
    """The registered ``variant`` sibling of ``name``, or None.

    ``variant_of("pallas-interpret", "pipelined")`` ->
    ``pallas-interpret-pipelined``; the input may itself be a variant name
    (its suffix is stripped first, so siblings map to each other);
    ``variant="plain"`` maps back to the base name.  Backends without the
    requested lowering (e.g. ``xla-reference``) map to None.
    """
    base = _base_name(name)
    cand = base if variant == "plain" else f"{base}-{variant}"
    return cand if cand in _REGISTRY else None


def pipelined_variant(name: str) -> Optional[str]:
    """The registered double-buffered sibling of ``name``, or None.

    Deprecated spelling of ``variant_of(name, "pipelined")`` (kept for the
    bool-era API surface): ``pallas-interpret`` ->
    ``pallas-interpret-pipelined``; a name that is already pipelined maps to
    itself; backends without a pipelined lowering (e.g. ``xla-reference``)
    map to None.
    """
    return variant_of(name, "pipelined")


def resolve_backend(name: Optional[str] = None, pipelined: bool = False,
                    variant: Optional[str] = None
                    ) -> "tuple[str, int, BackendTraits]":
    """One resolution rule for every executor: ``(name, version, traits)``.

    ``name=None`` picks the platform default.  ``variant`` resolves the
    named kernel-variant sibling ("plain" resolves the base name, so an
    explicitly plain request strips a variant suffix off ``name``);
    ``variant=None`` leaves ``name`` untouched and defers to the deprecated
    ``pipelined`` bool, which resolves the ``-pipelined`` sibling when True.
    A missing lowering raises (silently running a different kernel is never
    acceptable).
    """
    name = name or default_backend_name()
    if variant is None and pipelined:
        variant = "pipelined"
    if variant is not None and variant != "plain":
        sibling = variant_of(name, variant)
        if sibling is None:
            raise ValueError(
                f"backend {name!r} has no {variant} lowering; "
                f"variant={variant!r} (or pipelined=True) would silently "
                f"run the plain kernel — pick a pallas backend (their "
                f"-pipelined/-temporal siblings are registered) or drop "
                f"the variant request")
        name = sibling
    elif variant == "plain":
        base = variant_of(name, "plain")
        if base is not None:
            name = base
    _, version = get_backend(name)
    return name, version, backend_traits(name, version)


def lower(program, plan: Optional[BlockPlan] = None, *,
          coeffs=None, backend: Optional[str] = None,
          version: Optional[int] = None,
          grid_shape=None) -> LoweredStencil:
    """Lower a program (or legacy spec) through a registered backend.

    ``plan`` defaults to the perf-model's pick (paper §V.A tuning loop) for
    plan-driven backends; ``coeffs`` defaults to ``program.default_coeffs()``.
    """
    prog = as_program(program)
    if coeffs is None:
        c = prog.default_coeffs()
    else:
        c = normalize_coeffs(prog, coeffs)
    name = backend or default_backend_name()
    factory, v = get_backend(name, version)
    if plan is None and name != "xla-reference":
        plan = plan_blocking(prog, grid_shape=grid_shape,
                             compiled=backend_traits(name, v).compiled).plan
    lowered = factory(prog, plan, c)
    lowered.backend_name = name
    lowered.backend_version = v
    return lowered
