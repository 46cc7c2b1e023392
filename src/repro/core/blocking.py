"""Spatial + temporal blocking plans (paper §III/§V, adapted to VMEM).

The paper's knobs are (bsize, par_vec, par_time); ours are
(block_shape, par_time).  ``par_vec`` has no direct TPU analogue — the VPU
always operates on (8, 128) register tiles, so "vectorization" is subsumed by
keeping the minor block dim a multiple of 128 (the paper's eq. 6 alignment
restriction maps to our lane/sublane alignment preference).

Key equation (paper eq. 2), unchanged:

    csize_d = bsize_d - 2 * par_time * radius

i.e. a block that goes through ``par_time`` in-VMEM time steps loses
``par_time * radius`` of valid output per side — overlapped temporal blocking.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Optional, Sequence, Tuple

from repro.analysis.hw import TpuChip, V5E
from repro.core.program import StencilProgram, as_program
from repro.core.spec import StencilSpec

SUBLANE = 8
LANE = 128

# Overlapped-blocking tax floor shared by this planner and the autotuner's
# space enumeration (repro.tuning.space): plans keeping fewer than this
# fraction of their streamed window as useful output never win.
MIN_USEFUL_FRACTION = 0.25

# Kernel-variant axis shared by the backend registry, the tuner, and both
# planners (this module cannot import the registry without a cycle, so the
# canonical names live here):
#   "plain"     — one revolving window per block, one superstep per launch.
#   "pipelined" — double-buffered prefetch (two revolving windows).
#   "temporal"  — superstep chunking: TEMPORAL_CHUNK supersteps fused into a
#                 single kernel launch over a chunk-deep halo ring, so the
#                 carry ping-pong and the per-block window stream are paid
#                 once per chunk instead of once per superstep.
VARIANTS = ("plain", "pipelined", "temporal")

#: Supersteps fused per temporal-variant kernel launch (the chunk depth C).
#: One launch loads block + 2*C*halo per axis into VMEM and applies
#: C * par_time stencil steps with shrinking valid regions, writing only the
#: final block back — per-superstep HBM traffic ~1/C of the plain kernel's.
TEMPORAL_CHUNK = 4


def tile_alignment(ndim: int, compiled: bool,
                   dtype: str = "float32") -> Tuple[int, ...]:
    """Per-axis alignment of a compiled kernel's HBM DMA windows.

    Mosaic tiles the last two axes of an HBM array by the register tile
    (second minor by ``SUBLANE`` rows per 32-bit word, minor by ``LANE``)
    and refuses any DMA slice whose start or extent is not a tile multiple.
    Leading axes are untiled.  The interpreter has no tiling, so interpret
    mode keeps every axis at alignment 1 (the exact halo geometry).
    """
    if not compiled:
        return (1,) * ndim
    itemsize = 4 if dtype == "float32" else 2
    return (1,) * (ndim - 2) + (SUBLANE * (4 // itemsize), LANE)


def guard_rows(halo_radius: int) -> int:
    """Rows of slack above and below a VMEM frame: the strip compute's
    sublane taps read up to ``halo_radius`` rows past the frame, and a
    guard of whole row tiles keeps every compiled strip load aligned."""
    return round_up(halo_radius, SUBLANE)


def frame_buffer_shape(block_shape: Tuple[int, ...], ring: int,
                       align: Tuple[int, ...],
                       halo_radius: int) -> Tuple[int, ...]:
    """Shape of one VMEM frame buffer of the padded-carry kernel.

    The frame is the block plus the ring on both sides, the ring rounded
    up to ``align`` per axis so the frame DMA stays tile-aligned; the
    second-minor axis also carries :func:`guard_rows` on both sides.
    """
    shape = [b + 2 * round_up(ring, a) for b, a in zip(block_shape, align)]
    shape[-2] += 2 * guard_rows(halo_radius)
    return tuple(shape)


def normalize_variant(variant=None, pipelined: bool = False) -> str:
    """One rule for the ``pipelined: bool`` -> ``variant: str`` migration.

    A string names the variant directly; a bool (the deprecated knob) maps
    True -> "pipelined" / False -> "plain"; ``None`` defers to the
    ``pipelined`` argument.  Unknown strings raise.
    """
    if variant is None:
        variant = bool(pipelined)
    if variant is True:
        return "pipelined"
    if variant is False:
        return "plain"
    if variant not in VARIANTS:
        raise ValueError(
            f"unknown kernel variant {variant!r}; expected one of {VARIANTS}")
    return variant


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """A concrete blocking configuration for the temporal-blocked kernel.

    spec:        a ``StencilSpec`` (legacy) or ``StencilProgram``; halo and
                 FLOP accounting are derived from its tap set.
    block_shape: the *output* tile each pallas grid step produces (csize).
    par_time:    time steps fused per HBM round trip.
    halo:        par_time * halo_radius (per side), where halo_radius is the
                 max |offset| component over the tap set.
    """

    spec: StencilSpec
    block_shape: Tuple[int, ...]
    par_time: int

    @property
    def program(self) -> StencilProgram:
        return as_program(self.spec)

    @property
    def halo(self) -> int:
        return self.par_time * self.program.halo_radius

    @property
    def padded_shape(self) -> Tuple[int, ...]:
        return tuple(b + 2 * self.halo for b in self.block_shape)

    @property
    def vmem_bytes(self) -> int:
        """Two revolving buffers (paper's PE chain is a double buffer here)."""
        itemsize = 4 if self.spec.dtype == "float32" else 2
        padded = math.prod(self.padded_shape)
        return 2 * padded * itemsize

    def vmem_bytes_for(self, variant="plain", compiled: bool = True) -> int:
        """Variant-aware VMEM scratch of the compiled padded-carry kernel.

        Every variant steps the fused time steps between two frame buffers
        (the DMA target and a work frame); the ``-pipelined`` kernel adds a
        second DMA target so block g+1 streams in while g computes.  The
        ``-temporal`` frames carry the *chunk-deep* ring
        (``TEMPORAL_CHUNK * halo``), because one launch fuses
        ``TEMPORAL_CHUNK`` supersteps.  Frames carry guard rows and, for
        ``compiled`` kernels, a ring rounded to the register tile
        (:func:`frame_buffer_shape`); the interpreter keeps the exact ring.
        ``vmem_bytes`` (two bare windows) is the historical bound.
        ``variant`` also accepts the legacy bool.
        """
        prog = self.program
        itemsize = 4 if self.spec.dtype == "float32" else 2
        v = normalize_variant(variant)
        ring = self.halo * (TEMPORAL_CHUNK if v == "temporal" else 1)
        align = tile_alignment(prog.ndim, compiled, self.spec.dtype)
        frame = math.prod(frame_buffer_shape(self.block_shape, ring, align,
                                             prog.halo_radius))
        frames = 3 if v == "pipelined" else 2
        return itemsize * frames * frame

    # ---- redundancy accounting (paper's overlapped blocking cost) ----------
    #
    # ``compiled`` selects the kernel being charged, as in vmem_bytes_for.
    # A compiled padded-carry launch DMAs its whole frame (the block plus a
    # tile-rounded ring) and sweeps all of it at every fused step: the
    # kernel does not shrink its region.  Interpreter frames carry the
    # exact ring, and their cost stays the exact-halo trapezoid of the
    # paper's overlapped blocking (eq. 2), each step's region shrinking by
    # ``halo_radius`` per side.

    def ring(self, compiled: bool = True) -> Tuple[int, ...]:
        """Per-axis ring a launch carries around its block: the halo,
        rounded up to the register tile when ``compiled``
        (:func:`tile_alignment`)."""
        align = tile_alignment(self.program.ndim, compiled, self.spec.dtype)
        return tuple(round_up(self.halo, a) for a in align)

    def frame_shape(self, compiled: bool = True) -> Tuple[int, ...]:
        """The window one launch DMAs in: the block plus :meth:`ring` on
        both sides (the frame of ``kernels.common._launch_geometry``);
        ``padded_shape`` when not ``compiled``."""
        return tuple(b + 2 * g
                     for b, g in zip(self.block_shape, self.ring(compiled)))

    def cells_per_block(self, compiled: bool = True) -> int:
        """Cell-updates one launch computes over its ``par_time`` steps.

        Compiled: ``par_time`` sweeps of the frame — every row and lane,
        and in 3D the planes ``halo_radius .. Z - halo_radius``
        (``kernels.common._apply_step``).  Interpreter: the exact-halo
        trapezoid, step t computing ``padded - 2 * (t + 1) * halo_radius``
        per axis.
        """
        r = self.program.halo_radius
        if compiled:
            frame = list(self.frame_shape(compiled))
            if len(frame) == 3:
                frame[0] -= 2 * r
            return self.par_time * math.prod(frame)
        return sum(math.prod(p - 2 * (t + 1) * r for p in self.padded_shape)
                   for t in range(self.par_time))

    def useful_fraction_for(self, compiled: bool = True) -> float:
        """Useful cell-updates over computed ones, per launch — the
        overlapped-blocking tax (:data:`MIN_USEFUL_FRACTION` prunes on
        it).  Compiled: the block over one sweep of the frame; otherwise
        ``useful_fraction``."""
        if not compiled:
            return self.useful_fraction
        return self.useful_cells_per_block() / self.cells_per_block(True)

    @property
    def useful_fraction(self) -> float:
        """csize/bsize per axis, multiplied — the overlapped-blocking tax
        of exact-halo windows."""
        frac = 1.0
        for b, p in zip(self.block_shape, self.padded_shape):
            frac *= b / p
        return frac

    def hbm_bytes_per_block(self, compiled: bool = True) -> int:
        """One read of the launch's frame plus one write of its block."""
        itemsize = 4 if self.spec.dtype == "float32" else 2
        read = math.prod(self.frame_shape(compiled)) * itemsize
        write = math.prod(self.block_shape) * itemsize
        return read + write

    def blocks_per_superstep(self, grid_shape: Tuple[int, ...]) -> int:
        """Launches one superstep makes over ``grid_shape``, rounded up to
        whole blocks."""
        return math.prod(round_up(g, b) // b
                         for g, b in zip(grid_shape, self.block_shape))

    def run_bytes_per_superstep(self, grid_shape: Tuple[int, ...],
                                variant: str = "plain",
                                compiled: bool = True) -> int:
        """HBM bytes one fused-run superstep moves for ``grid_shape``.

        The padded-carry executor's stream is the kernel's own traffic —
        every block's overlapping frame read plus its tile write
        (``hbm_bytes_per_block``).  A compiled launch moves nothing else:
        it DMAs its frame out of one ping-pong buffer and its tile into
        the other.  The interpreter's superstep also passes over each of
        the two padded buffers once (the compiler's byte counter sees it;
        tests/test_padded_carry.py calibrates this model against it).  No
        O(volume) re-pad term: that is precisely what the padded layout
        eliminated.

        ``variant="temporal"`` charges one chunk-deep launch (halo ring and
        window ``TEMPORAL_CHUNK`` times deeper) amortized over the
        ``TEMPORAL_CHUNK`` supersteps it advances — the ~1/C marginal-traffic
        claim the traffic guard in tests/test_temporal_variant.py pins.
        """
        if normalize_variant(variant) == "temporal":
            deep = dataclasses.replace(
                self, par_time=self.par_time * TEMPORAL_CHUNK)
            return deep.run_bytes_per_superstep(
                grid_shape, compiled=compiled) // TEMPORAL_CHUNK
        kernel = self.blocks_per_superstep(grid_shape) \
            * self.hbm_bytes_per_block(compiled)
        if compiled:
            return kernel
        itemsize = 4 if self.spec.dtype == "float32" else 2
        padded_carry = math.prod(round_up(g, b) + 2 * self.halo for g, b in
                                 zip(grid_shape, self.block_shape))
        return kernel + 2 * padded_carry * itemsize

    def compute_redundancy(self, grid_shape: Tuple[int, ...],
                           variant: str = "plain",
                           compiled: bool = True) -> float:
        """Cell-updates computed per superstep over the useful ones of
        ``grid_shape`` (its round-up to whole blocks included): 1.0 is no
        redundant work.  ``variant="temporal"`` charges the chunk-deep
        launch."""
        plan = self if normalize_variant(variant) != "temporal" else \
            dataclasses.replace(self, par_time=self.par_time * TEMPORAL_CHUNK)
        computed = plan.blocks_per_superstep(grid_shape) \
            * plan.cells_per_block(compiled)
        return computed / (math.prod(grid_shape) * plan.par_time)

    def flops_per_block(self, compiled: bool = True) -> int:
        """FLOPs of :meth:`cells_per_block`."""
        return self.cells_per_block(compiled) * self.program.flops_per_cell

    def useful_cells_per_block(self) -> int:
        return math.prod(self.block_shape) * self.par_time


@dataclasses.dataclass(frozen=True)
class PlanEstimate:
    plan: BlockPlan
    compute_s_per_block: float
    hbm_s_per_block: float
    gcells_per_s: float        # useful cell-updates/s for one chip
    gflops_per_s: float        # useful FLOP/s (paper convention: no redundancy counted)
    bound: str                 # "compute" | "memory"


def estimate(plan: BlockPlan, hw: TpuChip = V5E,
             compiled: bool = True) -> PlanEstimate:
    """Single-chip throughput model = max(compute, HBM) per block round trip.

    Mirrors the paper's model role: predict useful throughput of a blocking
    configuration before committing to it (their place-and-route, our
    lower/compile).  ``compiled`` charges the compiled kernel's frame
    (see ``BlockPlan.cells_per_block``); the interpreter's otherwise.
    """
    t_compute = plan.flops_per_block(compiled) / hw.peak_vpu_f32_flops
    t_hbm = plan.hbm_bytes_per_block(compiled) / hw.hbm_bytes_per_s
    t = max(t_compute, t_hbm)
    useful = plan.useful_cells_per_block()
    gcells = useful / t
    return PlanEstimate(
        plan=plan,
        compute_s_per_block=t_compute,
        hbm_s_per_block=t_hbm,
        gcells_per_s=gcells,
        gflops_per_s=gcells * plan.spec.flops_per_cell,
        bound="compute" if t_compute >= t_hbm else "memory",
    )


def model_order(rate: float, hbm_bytes_per_cell: float, halo_aligned: bool,
                vmem_bytes: int) -> tuple:
    """Sort key of a modelled plan, best first under ``reverse=True``.

    The predicted rate comes first, to 12 significant digits: plans that
    the model prices equally (compute-bound at one redundancy, as compiled
    plans sharing a frame are) otherwise differ only by float rounding.
    Ties go to the plan that moves fewer HBM bytes per useful cell-update
    (a plain launch does not overlap its DMA with compute), then to a
    sublane-aligned halo (the paper's eq. 6 trick), then to the smaller
    VMEM footprint.
    """
    return (float(f"{rate:.12g}"), -hbm_bytes_per_cell, halo_aligned,
            -vmem_bytes)


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def grid_useful_fraction(grid_shape: Optional[Tuple[int, ...]],
                         block_shape: Tuple[int, ...]) -> float:
    """Fraction of block compute landing inside the grid (1.0 = no padding
    waste): output tiles that don't divide the grid evenly pad it up, and
    padded cells are wasted work.  1.0 when the grid is unknown."""
    if grid_shape is None:
        return 1.0
    frac = 1.0
    for g, b in zip(grid_shape, block_shape):
        frac *= g / round_up(g, b)
    return frac


def candidate_plans(
    spec: StencilSpec,
    hw: TpuChip = V5E,
    max_par_time: int = 64,
    block_candidates: Optional[Sequence[Tuple[int, ...]]] = None,
    pipelined: bool = False,
    variant: Optional[str] = None,
    compiled: bool = True,
) -> list:
    """Enumerate alignment-respecting plans that fit the VMEM budget.

    Alignment: minor dim multiples of LANE, second-minor multiples of SUBLANE
    (our analogue of paper eq. 6).  par_time preferred such that
    (par_time * radius) % SUBLANE == 0 — exactly their alignment trick with
    4 -> 8 for the TPU sublane.

    ``variant`` selects the kernel variant being planned for (``pipelined``
    is the deprecated bool spelling): the double-buffered kernel's two
    revolving windows halve the feasible block volume, the temporal kernel's
    chunk-deep window shrinks it further still, so plain-kernel plans are
    pruned against the one-window bound (``BlockPlan.vmem_bytes_for``).
    Temporal plans are additionally pruned by the *chunk-deep* overlap tax —
    the redundancy a temporal launch actually pays.  ``compiled`` sizes
    frames and the tax for the compiled kernel (tile-rounded ring) or the
    interpreter (exact ring).
    """
    v = normalize_variant(variant, pipelined)
    if block_candidates is None:
        if spec.ndim == 2:
            dims = [128, 256, 512, 1024, 2048]
            block_candidates = [(a, b) for a in dims for b in dims]
        else:
            zs = [8, 16, 32, 64]
            ys = [64, 128, 256]
            xs = [128, 256, 512]
            block_candidates = [(z, y, x) for z in zs for y in ys for x in xs]

    plans = []
    for bs in block_candidates:
        for pt in range(1, max_par_time + 1):
            plan = BlockPlan(spec=spec, block_shape=tuple(bs), par_time=pt)
            if plan.vmem_bytes_for(v, compiled) > hw.vmem_budget_bytes:
                continue
            tax_plan = plan if v != "temporal" else dataclasses.replace(
                plan, par_time=pt * TEMPORAL_CHUNK)
            if tax_plan.useful_fraction_for(compiled) <= MIN_USEFUL_FRACTION:
                continue  # overlapped-blocking tax beyond any win
            plans.append(plan)
    return plans


def plan_blocking(
    spec: StencilSpec,
    hw: TpuChip = V5E,
    grid_shape: Optional[Tuple[int, ...]] = None,
    max_par_time: int = 64,
    pipelined: bool = False,
    variant: Optional[str] = None,
    compiled: bool = True,
) -> PlanEstimate:
    """Pick the best plan by the model — the paper's §V.A tuning loop.

    Preference order: highest predicted useful GCell/s, ties broken by
    :func:`model_order`.

    This is the *model-only, zero-dependency* planner behind
    ``backends.lower(plan=None)``; ``repro.tuning`` is its superset
    (bsize-space enumeration + empirical measurement + plan cache) and
    cannot be imported from here without a cycle through the backend
    registry.  Shared pieces (``MIN_USEFUL_FRACTION``, ``round_up``,
    ``grid_useful_fraction``, ``model_order``, the VMEM predicate on
    ``vmem_budget_bytes``) live in this module so the two cannot drift.
    ``compiled`` says which kernel runs the plan: the compiled one (the
    default) or the interpreter, whose frames carry the exact ring.
    """
    v = normalize_variant(variant, pipelined)
    best = None
    for plan in candidate_plans(spec, hw, max_par_time=max_par_time,
                                variant=v, compiled=compiled):
        # A temporal launch streams the chunk-deep window and advances
        # TEMPORAL_CHUNK supersteps: estimate() on the chunk-deep plan IS
        # that launch's model, and its useful-GCell/s are directly
        # comparable to a plain superstep's.  The returned plan keeps the
        # caller-visible par_time.
        launch = plan if v != "temporal" else dataclasses.replace(
            plan, par_time=plan.par_time * TEMPORAL_CHUNK)
        est = dataclasses.replace(estimate(launch, hw, compiled), plan=plan)
        # blocks larger than the grid still work (the kernel pads), but
        # padded cells are wasted compute — penalize them.
        useful = grid_useful_fraction(grid_shape, plan.block_shape)
        key = model_order(est.gcells_per_s * useful,
                          launch.hbm_bytes_per_block(compiled)
                          / launch.useful_cells_per_block(),
                          (plan.halo % SUBLANE) == 0, plan.vmem_bytes)
        if best is None or key > best[0]:
            best = (key, est)
    if best is None:
        raise ValueError("no feasible blocking plan (VMEM budget too small?)")
    return best[1]
