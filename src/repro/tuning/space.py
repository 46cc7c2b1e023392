"""Design-space enumeration for the autotuner (paper §V.A, eqs. 2/4/5/6).

The paper tunes (bsize, par_vec, par_time) for an FPGA; we tune
(bsize, par_time, backend) for a TPU.  Enumeration works in **bsize space**
— the padded input window one superstep streams from HBM — exactly like the
paper, and derives the useful output tile by eq. 2:

    csize_d = bsize_d - 2 * par_time * halo_radius        (per axis)

The paper's feasibility constraints map onto TPU pruning predicates:

  paper eq. 2  csize > 0            -> :func:`eq2_csize` returning None
  paper eq. 4/5 DSP/BRAM budget     -> :func:`fits_vmem` (the on-chip SRAM
                                       that bounds how deep a block can go)
  paper eq. 6  DDR burst alignment  -> :func:`is_aligned` on bsize (minor %
                                       LANE, second-minor % SUBLANE); the
                                       (par_time*rad) % SUBLANE == 0 variant
                                       is kept as a *soft* ranking signal
                                       (``Candidate.halo_aligned``), the
                                       paper's own 4 -> 8 alignment trick
  (ours)       overlap-tax floor    -> ``min_useful_fraction``: overlapped
                                       blocking past ~4x redundancy never
                                       wins (paper Fig. 3's falling edge)

``par_vec`` has no free TPU analogue (the VPU always runs (8, 128) tiles);
it is absorbed by the lane-alignment predicate — see DESIGN.md §6.

Mesh-aware enumeration (the SASA direction — hybrid spatial/temporal
parallelism across parallel memory channels, here the device mesh): with
``n_devices`` (or explicit ``decompositions``) the space gains a
*decomposition axis* — every way of factoring the device count over the
grid's dimensions — and each (plan, decomposition) pair is pruned by the
per-shard analogue of eq. 2: the ``par_time * halo_radius``-deep exchange
halo must fit the *local* shard extent (and the local extent must tile by
csize), exactly the feasibility checks ``DistributedStencil`` enforces at
construction.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Iterator, List, Optional, Sequence, Tuple

from repro.analysis.hw import TpuChip, V5E
from repro.backends.registry import (backend_traits, default_backend_name,
                                     get_backend, variant_of)
from repro.core.blocking import (LANE, MIN_USEFUL_FRACTION, SUBLANE,
                                 TEMPORAL_CHUNK, VARIANTS, BlockPlan,
                                 normalize_variant, round_up,
                                 tile_alignment)
from repro.core.program import as_program

Shape = Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class MeshDecomposition:
    """Shards per grid axis — how a device mesh is laid over the grid.

    Mesh axis *names* are a runtime concern (``core.distributed``); for
    tuning only the shard count per grid dimension matters, so two mesh
    layouts yielding the same per-axis split are one point of the space.
    """

    axis_shards: Shape

    def __post_init__(self):
        if not self.axis_shards or any(s < 1 for s in self.axis_shards):
            raise ValueError(f"bad axis_shards {self.axis_shards}")

    @property
    def n_devices(self) -> int:
        return math.prod(self.axis_shards)

    def local_shape(self, grid_shape: Shape) -> Shape:
        return tuple(g // s for g, s in zip(grid_shape, self.axis_shards))

    def describe(self) -> str:
        return "x".join(map(str, self.axis_shards))


def _factorizations(n: int, ndim: int) -> Iterator[Shape]:
    """All ordered factorizations of ``n`` into ``ndim`` positive factors."""
    if ndim == 1:
        yield (n,)
        return
    for d in range(1, n + 1):
        if n % d == 0:
            for rest in _factorizations(n // d, ndim - 1):
                yield (d,) + rest


def enumerate_decompositions(ndim: int, n_devices: int,
                             grid_shape: Optional[Shape] = None
                             ) -> List[MeshDecomposition]:
    """Every way of splitting ``n_devices`` over ``ndim`` grid axes.

    With a grid, splits that do not divide an axis evenly are dropped (the
    runtime refuses them — ``DistributedStencil``'s divisibility check).
    """
    out = []
    for shards in _factorizations(n_devices, ndim):
        if grid_shape is not None and any(
                g % s != 0 for g, s in zip(grid_shape, shards)):
            continue
        out.append(MeshDecomposition(axis_shards=shards))
    return out


def shard_violations(plan: BlockPlan, decomp: MeshDecomposition,
                     grid_shape: Shape) -> List[str]:
    """Why a (plan, decomposition) pair is per-shard infeasible — [] if fine.

    The reason strings feed the static verifier's RP107 diagnostics
    (``repro.lint``); :func:`fits_shard` is the boolean view the
    enumeration loops prune on.  One rule set, two consumers.
    """
    bad: List[str] = []
    for d, (g, s, c) in enumerate(zip(grid_shape, decomp.axis_shards,
                                      plan.block_shape)):
        if g % s != 0:
            bad.append(f"axis {d}: grid extent {g} does not divide into "
                       f"{s} shards")
            continue
        local = g // s
        if local % c != 0:
            bad.append(f"axis {d}: local extent {local} does not tile by "
                       f"csize {c}")
        if local < plan.halo:
            bad.append(f"axis {d}: exchange halo {plan.halo} "
                       f"(par_time={plan.par_time} x halo_radius) is deeper "
                       f"than the local extent {local}")
    return bad


def fits_shard(plan: BlockPlan, decomp: MeshDecomposition,
               grid_shape: Shape) -> bool:
    """Per-shard feasibility — eq. 2 applied to the local extent.

    Mirrors ``DistributedStencil.__post_init__``: every sharded axis must
    split evenly, the local extent must tile by the output block (csize),
    and the ``par_time * halo_radius``-deep exchange halo must not exceed
    the local extent (the strips ppermute'd to neighbors are cut from the
    local block, so a halo deeper than the shard is unsatisfiable).
    """
    return not shard_violations(plan, decomp, grid_shape)


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One legal point of the design space: a blocking plan on a backend,
    optionally placed on a mesh decomposition.

    ``plan.block_shape`` is the eq. 2 csize (useful output tile);
    ``plan.padded_shape`` reproduces the enumerated bsize.  ``decomp`` is
    None for single-device candidates.
    """

    plan: BlockPlan
    backend: str
    backend_version: int
    halo_aligned: bool     # (par_time * halo_radius) % SUBLANE == 0 (soft eq. 6)
    decomp: Optional[MeshDecomposition] = None
    variant: str = "plain"  # kernel lowering: "plain" | "pipelined" | "temporal"

    @property
    def bsize(self) -> Shape:
        return self.plan.padded_shape

    @property
    def csize(self) -> Shape:
        return self.plan.block_shape

    @property
    def par_time(self) -> int:
        return self.plan.par_time

    @property
    def compiled(self) -> bool:
        """The backend runs the compiled kernel (tile-rounded frames)."""
        return backend_traits(self.backend, self.backend_version).compiled

    def describe(self) -> str:
        mesh = "" if self.decomp is None \
            else f" mesh={self.decomp.describe()}"
        return (f"bsize={'x'.join(map(str, self.bsize))} "
                f"csize={'x'.join(map(str, self.csize))} "
                f"par_time={self.par_time} backend={self.backend}"
                f"@v{self.backend_version}{mesh}")


# ---- pruning predicates (each maps one paper constraint) -------------------

def eq2_csize(bsize: Shape, par_time: int, halo_radius: int,
              align: Optional[Shape] = None) -> Optional[Shape]:
    """Paper eq. 2 per axis; None when any axis has csize <= 0.

    ``align`` (:func:`repro.core.blocking.tile_alignment`) rounds the halo
    up per axis, as a compiled kernel's carry ring is: an aligned window
    then leaves an aligned csize, so every block the kernel DMAs starts on
    a register tile.
    """
    align = align or (1,) * len(bsize)
    cs = tuple(b - 2 * round_up(par_time * halo_radius, a)
               for b, a in zip(bsize, align))
    return cs if all(c > 0 for c in cs) else None


def is_aligned(bsize: Shape) -> bool:
    """TPU analogue of paper eq. 6: the streamed window must land on
    register-tile boundaries — minor dim a multiple of LANE (128), second
    minor a multiple of SUBLANE (8).  Leading (z) dims are unconstrained."""
    return bsize[-1] % LANE == 0 and bsize[-2] % SUBLANE == 0


def fits_vmem(plan: BlockPlan, chip: TpuChip,
              pipelined: bool = False,
              variant: Optional[str] = None,
              compiled: bool = True) -> bool:
    """Paper eq. 4/5 analogue: the kernel's VMEM scratch must fit the
    planner's budget (their DSP/BRAM caps, our on-chip SRAM cap).

    Variant-aware: the ``-pipelined`` kernel revolves two halo'd window
    buffers, the plain kernel just one, and the ``-temporal`` kernel's
    single window is ``TEMPORAL_CHUNK`` halo rings deeper — pruning plain
    plans with the double-buffered bound would forfeit bigger blocks /
    deeper par_time.  ``variant`` names the lowering; ``None`` defers to
    the deprecated ``pipelined`` bool.  ``compiled`` sizes the frames with
    the tile-rounded ring of a compiled kernel.
    """
    v = normalize_variant(variant, pipelined)
    return plan.vmem_bytes_for(v, compiled) <= chip.vmem_budget_bytes


def halo_aligned(par_time: int, halo_radius: int) -> bool:
    """Paper's own eq. 6 trick (pad 4 -> 8): prefer supersteps whose halo
    depth is sublane-aligned.  Soft — recorded on the candidate for ranking
    tie-breaks, never used to prune."""
    return (par_time * halo_radius) % SUBLANE == 0


def _aligned_divisors(n: int, align: int) -> List[int]:
    """Divisors of ``n`` that are multiples of ``align``, ascending."""
    return [d for d in range(align, n + 1, align) if n % d == 0]


# ---- bsize candidates ------------------------------------------------------

# Static per-axis sweeps sized for paper-scale grids (the paper sweeps
# bsize_x in {1024..8192}); minor axis LANE-aligned, second minor
# SUBLANE-aligned by construction.
_AXIS_OPTIONS_2D = ((128, 256, 512, 1024, 2048),
                    (512, 1024, 2048, 4096))
_AXIS_OPTIONS_3D = ((8, 16, 32, 64),
                    (32, 64, 128, 256),
                    (256, 512, 1024))


def default_bsizes(ndim: int,
                   grid_shape: Optional[Shape] = None) -> Tuple[Shape, ...]:
    """Padded-window candidates.

    The static per-axis sweep, plus — when a grid is given — windows derived
    from the grid extents (full / half / quarter per axis, rounded up to
    alignment) so tiny CI grids still yield a non-degenerate space; static
    options larger than the (rounded-up) grid axis are dropped as pure
    padding waste.
    """
    static = _AXIS_OPTIONS_2D if ndim == 2 else _AXIS_OPTIONS_3D
    if grid_shape is None:
        return tuple(itertools.product(*static))
    if len(grid_shape) != ndim:
        raise ValueError(f"grid_shape {grid_shape} is not {ndim}-D")
    per_axis: List[Tuple[int, ...]] = []
    for d, g in enumerate(grid_shape):
        if d == ndim - 1:
            align = LANE
        elif d == ndim - 2:
            align = SUBLANE
        else:
            align = 4
        cap = round_up(g, align)
        opts = {round_up(max(g // f, 1), align) for f in (1, 2, 4)}
        opts.update(o for o in static[d] if o <= cap)
        per_axis.append(tuple(sorted(opts)))
    return tuple(itertools.product(*per_axis))


# ---- the legal space -------------------------------------------------------

def enumerate_space(
    program,
    chip: TpuChip = V5E,
    *,
    backends: Optional[Sequence[str]] = None,
    backend_version: Optional[int] = None,
    bsizes: Optional[Sequence[Shape]] = None,
    grid_shape: Optional[Shape] = None,
    max_par_time: int = 32,
    min_useful_fraction: float = MIN_USEFUL_FRACTION,
    n_devices: Optional[int] = None,
    decompositions: Optional[Sequence[MeshDecomposition]] = None,
) -> List[Candidate]:
    """All legal (bsize, par_time, backend[, decomposition]) points for
    ``program`` on ``chip``.

    Every returned candidate satisfies eq. 2 (positive csize on every axis),
    the bsize alignment predicate, and the VMEM budget; candidates whose
    useful fraction (``BlockPlan.useful_fraction_for``: the block over the
    frame the backend's kernel computes) falls below
    ``min_useful_fraction`` are pruned as unwinnable redundancy.

    ``n_devices`` (or explicit ``decompositions``) turns on the mesh
    decomposition axis: the cross product of the blocking space with every
    feasible device split, pruned per shard by :func:`fits_shard` — this
    requires ``grid_shape`` (local extents are meaningless without it).
    """
    prog = as_program(program)
    r = prog.halo_radius

    decomps: Optional[Sequence[MeshDecomposition]] = decompositions
    if decomps is None and n_devices is not None:
        decomps = enumerate_decompositions(prog.ndim, n_devices, grid_shape)
    if decomps is not None:
        if grid_shape is None:
            raise ValueError(
                "mesh-aware enumeration needs grid_shape (per-shard halo "
                "pruning is relative to the local extent)")
        for dc in decomps:
            if len(dc.axis_shards) != prog.ndim:
                raise ValueError(
                    f"decomposition {dc.axis_shards} is not {prog.ndim}-D")

    explicit_bsizes = bsizes
    if bsizes is None:
        bsizes = default_bsizes(prog.ndim, grid_shape)
    if backends is None:
        # The kernel variant is a searchable axis: by default every blocking
        # point is enumerated on every registered lowering of the platform
        # backend — plain, double-buffered (-pipelined), and temporally
        # fused (-temporal) where they exist (the paper equally treats its
        # pipeline depth as part of the tuned configuration).  The roofline
        # model cannot separate plain from pipelined (same traffic, same
        # FLOPs), so a model-ranked top-K over this default space holds
        # fewer distinct blocking points than K — callers who measure
        # should scale top_k if they want the same blocking diversity, and
        # autotune() itself pins the variant axis per call/cache-key.
        base = default_backend_name()
        backends = tuple(
            n for n in (variant_of(base, v) for v in VARIANTS)
            if n is not None)

    resolved = []
    compiled = False
    for name in backends:
        version = get_backend(name, backend_version)[1]
        traits = backend_traits(name, version)
        resolved.append((name, version, traits.variant))
        compiled |= traits.compiled
    # a compiled Pallas kernel DMAs tile-aligned blocks through a
    # tile-rounded ring (csize per eq. 2 with the rounded halo)
    align = tile_alignment(prog.ndim, compiled, prog.dtype)

    out: List[Candidate] = []

    if decomps is not None and explicit_bsizes is None:
        # Mesh path, free blocking: the runtime demands the local extent
        # tile exactly by csize (no round-up under shard_map), so csize is
        # enumerated from the *aligned divisors of the local extent* per
        # decomposition — a global bsize sweep would mostly miss.  The
        # eq. 6 alignment predicate moves onto the output tile (the
        # streamed window is the halo-exchanged local block, whose
        # alignment follows csize + 2*halo and cannot be chosen freely).
        for dc in decomps:
            local = dc.local_shape(grid_shape)
            axis_opts = []
            for d in range(prog.ndim):
                if d == prog.ndim - 1:
                    align = LANE
                elif d == prog.ndim - 2:
                    align = SUBLANE
                else:
                    align = 1
                axis_opts.append(_aligned_divisors(local[d], align))
            for cs in itertools.product(*axis_opts):
                for pt in range(1, max_par_time + 1):
                    plan = BlockPlan(spec=prog, block_shape=cs, par_time=pt)
                    if not fits_shard(plan, dc, grid_shape):
                        break   # halo grows with pt: no recovery
                    if not fits_vmem(plan, chip, compiled=compiled):
                        break   # window = csize + 2*halo grows with pt
                    if plan.useful_fraction_for(compiled) \
                            <= min_useful_fraction:
                        break   # non-increasing in pt
                    for name, version, var in resolved:
                        # The temporal chunk advances TEMPORAL_CHUNK
                        # supersteps per launch but the mesh exchanges
                        # halos once per superstep — the executor refuses
                        # the pair, so the space never emits it.
                        if var == "temporal":
                            continue
                        # Variant-aware budget: the point may fit the plain
                        # kernel's single window but not the pipelined pair.
                        if not fits_vmem(plan, chip, variant=var,
                                         compiled=compiled):
                            continue
                        out.append(Candidate(plan=plan, backend=name,
                                             backend_version=version,
                                             halo_aligned=halo_aligned(pt, r),
                                             decomp=dc, variant=var))
        return out

    for bsize in bsizes:
        if len(bsize) != prog.ndim or not is_aligned(bsize):
            continue
        for pt in range(1, max_par_time + 1):
            cs = eq2_csize(bsize, pt, r, align)
            if cs is None:
                break                      # csize shrinks with pt: no recovery
            plan = BlockPlan(spec=prog, block_shape=cs, par_time=pt)
            if not fits_vmem(plan, chip, compiled=compiled):
                # The plain bound (window + shrinking output tile) decreases
                # with pt, so deeper supersteps may still fit: keep probing.
                continue
            if plan.useful_fraction_for(compiled) <= min_useful_fraction:
                break   # non-increasing in pt; boundary matches
                        # blocking.candidate_plans
            # Variant-aware budget: the point may fit the plain kernel's
            # single window but not the pipelined pair or the chunk-deep
            # temporal window; the temporal launch additionally pays the
            # *chunk-deep* overlap tax (eq. 2 with par_time*TEMPORAL_CHUNK
            # fused steps), so its redundancy floor is checked on the
            # deepened plan.
            fits = {var: fits_vmem(plan, chip, variant=var, compiled=compiled)
                    for _, _, var in resolved}
            if fits.get("temporal"):
                deep = dataclasses.replace(
                    plan, par_time=pt * TEMPORAL_CHUNK)
                if deep.useful_fraction_for(compiled) <= min_useful_fraction:
                    fits["temporal"] = False
            if decomps is not None:
                # Mesh path, explicit windows: keep the caller's bsize
                # semantics and prune each (plan, decomposition) pair by
                # the per-shard constraints.  Temporal never lands on a
                # mesh (chunked launches outrun the per-superstep halo
                # exchange — the executor refuses the pair).
                for dc in decomps:
                    if not fits_shard(plan, dc, grid_shape):
                        continue
                    for name, version, var in resolved:
                        if var == "temporal" or not fits[var]:
                            continue
                        out.append(Candidate(plan=plan, backend=name,
                                             backend_version=version,
                                             halo_aligned=halo_aligned(pt, r),
                                             decomp=dc, variant=var))
                continue
            for name, version, var in resolved:
                if not fits[var]:
                    continue
                out.append(Candidate(plan=plan, backend=name,
                                     backend_version=version,
                                     halo_aligned=halo_aligned(pt, r),
                                     variant=var))
    return out
