"""Shared Pallas machinery for the temporal-blocked stencil kernels.

TPU-native design (see DESIGN.md §2 for the FPGA -> TPU map):

* The input grid lives in HBM (``ANY`` memory space); each pallas grid step
  DMAs one *halo-extended* block into a VMEM scratch buffer — the analogue of
  the paper's shift-register fill.  Halo'd input windows overlap, which Blocked
  BlockSpecs cannot express, hence the manual ``make_async_copy``.
* ``par_time`` stencil applications run back-to-back on the VMEM-resident
  block (the paper's chained PEs), each shrinking the valid region by
  ``halo_radius`` — overlapped temporal blocking, eq. 2.
* After each fused step, out-of-grid positions are re-fixed according to the
  program's boundary mode (paper §III.B's generated boundary conditions):
  clamp re-reads the border cell, constant re-fills the boundary value, and
  periodic needs no fixup at all — a wrap-filled halo holds exact values of
  the periodic extension, which evolves under the same stencil as the grid.
  Without the clamp/constant fixup, pre-padded halos go stale after one step
  and orders >= 1 diverge at the boundary for par_time >= 2.
* The output block is written through a regular Blocked BlockSpec — output
  tiles never overlap.

The kernel bodies are generated from a :class:`StencilProgram` tap set —
star/box/diamond all lower through the same emitter (codegen.py).

The padded-carry kernels (the fused executor's path) are written for the
TPU compiler: every HBM DMA window is register-tile aligned (the carry ring
is rounded up to the tile on compiled backends, :func:`tile_alignment`),
the coefficients are SMEM scalars, and the fused steps run as row-strip
loops over VMEM frame buffers (sublane taps are offset loads, lane taps
lane rotations) instead of whole-block array values, whose unrolled code
grows with the block area.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocking import (  # noqa: F401 (re-export)
    LANE, SUBLANE, BlockPlan, TEMPORAL_CHUNK, guard_rows, normalize_variant,
    round_up, tile_alignment)
from repro.core.codegen import boundary_pad, tap_interior_update
from repro.core.program import ProgramCoeffs, StencilProgram

MemorySpace = pltpu.MemorySpace

#: VMEM scratch constructor — ``vmem_scratch(shape, dtype)``.
vmem_scratch = pltpu.VMEM

#: DMA semaphore scratch type.
dma_semaphore = pltpu.SemaphoreType.DMA


# ---- trace accounting ------------------------------------------------------
# Python-side counters bumped at *trace* time inside the jit'd entry points.
# A jit cache hit never re-traces, so the per-name count equals the number of
# executables built for that entry point since the last reset — the
# compile-count regression tests key off this (no jax.monitoring dependency).

_TRACE_COUNTS: Dict[str, int] = collections.Counter()
# Concurrent compiles (threaded serving fronts, parallel test workers) bump
# the same Counter; ``c[k] += 1`` is a read-modify-write, so without the
# lock two racing traces can lose an increment and the compile-count
# regression tests go flaky exactly when compiles overlap.
_TRACE_LOCK = threading.Lock()


def _note_trace(name: str) -> None:
    with _TRACE_LOCK:
        _TRACE_COUNTS[name] += 1


def trace_count(name: str) -> int:
    """How many times the named jit'd entry point traced since last reset."""
    with _TRACE_LOCK:
        return _TRACE_COUNTS.get(name, 0)


def trace_counts() -> Dict[str, int]:
    """Snapshot of every counter (the obs layer diffs these around runs)."""
    with _TRACE_LOCK:
        return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    with _TRACE_LOCK:
        _TRACE_COUNTS.clear()


def trace_delta(before: Dict[str, int]) -> Dict[str, int]:
    """Per-entry-point retrace counts since a ``trace_counts()`` snapshot.

    The obs layer attaches this to compile/run spans, and
    ``repro.lint.check_trace_budget`` turns a nonzero steady-state delta
    into an RP203 recompile-hazard diagnostic.
    """
    after = trace_counts()
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def batch_dims(program: StencilProgram, grid_ndim: int) -> int:
    """Number of leading batch axes on a grid: 0 (unbatched) or 1.

    The single rank rule for every batchable entry point (superstep, run,
    the xla-reference oracle): a grid may carry exactly one leading axis of
    independent grids on top of the program's spatial rank.
    """
    nb = grid_ndim - program.ndim
    if nb not in (0, 1):
        raise ValueError(
            f"grid rank {grid_ndim} does not match a {program.ndim}-D "
            f"program (expected {program.ndim} or {program.ndim + 1} with "
            f"a batch axis)")
    return nb


def boundary_fixup(program: StencilProgram, cur: jnp.ndarray, starts,
                   true_shape: Tuple[int, ...]):
    """Restore boundary semantics on out-of-grid positions between fused steps.

    ``starts[d]`` is the (traced) global coordinate of ``cur``'s origin along
    axis d; positions outside [0, true_shape[d]) are overwritten according to
    the program's boundary mode so the next fused time step reads correct
    halo values.  For fully-interior blocks every select is a no-op.

    periodic: no-op by construction — the halo was wrap-filled with the
    periodic extension, and the extension evolves under the same update as
    the grid, so it never goes stale.
    """
    if program.boundary == "periodic":
        return cur
    for d in range(cur.ndim):
        size = cur.shape[d]
        n = true_shape[d]
        pos = starts[d] + lax.broadcasted_iota(jnp.int32, cur.shape, d)
        if program.boundary == "constant":
            fill = jnp.asarray(program.boundary_value, cur.dtype)
            cur = jnp.where((pos < 0) | (pos > n - 1), fill, cur)
            continue
        # clamp: border-cell slabs (1-wide along axis d), indices clipped
        # into range so dynamic_slice never reads out of the buffer.
        left_idx = jnp.clip(-starts[d], 0, size - 1)
        right_idx = jnp.clip((n - 1) - starts[d], 0, size - 1)
        left = lax.dynamic_slice_in_dim(cur, left_idx, 1, axis=d)
        right = lax.dynamic_slice_in_dim(cur, right_idx, 1, axis=d)
        cur = jnp.where(pos < 0, left, cur)
        cur = jnp.where(pos > n - 1, right, cur)
    return cur


def _fused_steps(program: StencilProgram, plan: BlockPlan, coeffs, buf,
                 pids, offs_ref, true_shape):
    """Run ``par_time`` tap-set applications on a VMEM-resident block."""
    ndim = program.ndim
    block = plan.block_shape
    halo = plan.halo
    r = program.halo_radius
    T = plan.par_time
    cur = buf
    for t in range(1, T + 1):
        cur = tap_interior_update(program, coeffs, cur)
        if t < T:
            starts = tuple(
                offs_ref[d] + pids[d] * block[d] - halo + t * r
                for d in range(ndim))
            cur = boundary_fixup(program, cur, starts, true_shape)
    return cur


def build_superstep_kernel(program: StencilProgram, plan: BlockPlan,
                           true_shape: Tuple[int, ...],
                           batch: Optional[int] = None):
    """Returns the pallas kernel body for one superstep (par_time fused steps).

    ``true_shape`` is the *global* grid shape; the ``offs`` input carries this
    shard's global origin (all zeros on a single device), so boundary fixup
    happens exactly at the physical grid boundary even under domain
    decomposition.

    ``batch`` adds a leading pallas grid dimension over independent grids:
    the input is ``(B, *padded)``, the scratch window ``(1, *padded_block)``,
    and ``program_id(0)`` selects the grid while the spatial ids shift right
    by one.  Boundary fixup is per-grid (the batch axis has no taps, so it
    never participates in halo arithmetic).
    """
    ndim = program.ndim
    block = plan.block_shape
    padded_block = plan.padded_shape

    def kernel(offs_ref, c_ref, t_ref, in_ref, o_ref, buf_ref, sem):
        if batch is None:
            pids = [pl.program_id(d) for d in range(ndim)]
        else:
            pids = [pl.program_id(d + 1) for d in range(ndim)]
        window = tuple(
            pl.ds(pids[d] * block[d], padded_block[d]) for d in range(ndim))
        if batch is not None:
            window = (pl.ds(pl.program_id(0), 1),) + window
        cp = pltpu.make_async_copy(in_ref.at[window], buf_ref, sem)
        cp.start()
        cp.wait()

        coeffs = ProgramCoeffs(center=c_ref[0, 0], taps=t_ref[...][0])
        blk = buf_ref[...] if batch is None else buf_ref[0]
        res = _fused_steps(program, plan, coeffs, blk, pids, offs_ref,
                           true_shape)
        o_ref[...] = res if batch is None else res[jnp.newaxis]

    return kernel


def build_pipelined_kernel(program: StencilProgram, plan: BlockPlan,
                           true_shape: Tuple[int, ...],
                           grid: Tuple[int, ...],
                           batch: Optional[int] = None):
    """Double-buffered variant: the DMA for block g+1 is issued before block
    g's compute — the TPU-native analogue of the paper's deep pipeline
    (their PEs consume a stream while the next block fills the shift
    register).  Two VMEM buffers + two DMA semaphores alternate by grid
    parity; scratch persists across sequential grid steps on a TPU core.

    ``grid`` is the *spatial* block grid; with ``batch`` the iteration space
    becomes ``(batch, *grid)`` and prefetch streams across grid boundaries of
    consecutive batch entries too (the linearization folds the batch index in
    front, so block g+1 of the next grid is prefetched while the last block
    of the current grid computes).
    """
    ndim = program.ndim
    block = plan.block_shape
    padded_block = plan.padded_shape
    vgrid = grid if batch is None else (batch,) + tuple(grid)
    nd_all = len(vgrid)
    total = math.prod(vgrid)

    def _coords(lin):
        idx = []
        rem = lin
        for d in range(nd_all - 1, -1, -1):
            idx.append(rem % vgrid[d])
            rem = rem // vgrid[d]
        return tuple(reversed(idx))

    def kernel(offs_ref, c_ref, t_ref, in_ref, o_ref, buf0, buf1, sem0,
               sem1):
        ids = [pl.program_id(d) for d in range(nd_all)]
        lin = ids[0]
        for d in range(1, nd_all):
            lin = lin * vgrid[d] + ids[d]
        parity = jax.lax.rem(lin, 2)
        pids = ids if batch is None else ids[1:]

        def _copy(lin_idx, buf, sem):
            coords = _coords(lin_idx)
            sp = coords if batch is None else coords[1:]
            window = tuple(pl.ds(sp[d] * block[d], padded_block[d])
                           for d in range(ndim))
            if batch is not None:
                window = (pl.ds(coords[0], 1),) + window
            return pltpu.make_async_copy(in_ref.at[window], buf, sem)

        @pl.when(lin == 0)
        def _prologue():
            _copy(lin, buf0, sem0).start()

        nxt = lin + 1

        @pl.when((nxt < total) & (parity == 0))
        def _prefetch_odd():
            _copy(nxt, buf1, sem1).start()

        @pl.when((nxt < total) & (parity == 1))
        def _prefetch_even():
            _copy(nxt, buf0, sem0).start()

        coeffs = ProgramCoeffs(center=c_ref[0, 0], taps=t_ref[...][0])

        def _compute(buf, sem):
            _copy(lin, buf, sem).wait()
            blk = buf[...] if batch is None else buf[0]
            res = _fused_steps(program, plan, coeffs, blk, pids, offs_ref,
                               true_shape)
            o_ref[...] = res if batch is None else res[jnp.newaxis]

        @pl.when(parity == 0)
        def _run_even():
            _compute(buf0, sem0)

        @pl.when(parity == 1)
        def _run_odd():
            _compute(buf1, sem1)

    return kernel


def default_interpret() -> bool:
    """Pallas TPU kernels run in interpret mode on CPU hosts."""
    return jax.default_backend() != "tpu"


def _superstep_pallas(padded: jnp.ndarray, center: jnp.ndarray,
                      taps: jnp.ndarray, program: StencilProgram,
                      plan: BlockPlan, true_shape: Tuple[int, ...],
                      interpret: bool,
                      offsets: jnp.ndarray | None = None,
                      pipelined: bool = False) -> jnp.ndarray:
    """Build + invoke the pallas superstep over a pre-padded grid (untraced
    helper shared by :func:`superstep_call` and :func:`run_call` so the fused
    run executor never pays a second jit dispatch).

    ``padded`` is ``(rounded + 2*halo per axis)`` or batched
    ``(B, rounded + 2*halo per axis)``; an extra leading axis becomes a
    leading pallas grid dimension over independent grids.
    """
    ndim = program.ndim
    batch: Optional[int] = padded.shape[0] \
        if batch_dims(program, padded.ndim) else None
    block = plan.block_shape
    halo = plan.halo
    spatial = padded.shape[-ndim:]
    rounded = tuple(spatial[d] - 2 * halo for d in range(ndim))
    grid = tuple(rounded[d] // block[d] for d in range(ndim))

    if offsets is None:
        offsets = jnp.zeros((ndim,), jnp.int32)
    c2 = center.reshape((1, 1)).astype(padded.dtype)
    t2 = taps.reshape((1, -1)).astype(padded.dtype)

    buf_shape = plan.padded_shape if batch is None \
        else (1,) + plan.padded_shape
    if pipelined:
        kernel = build_pipelined_kernel(program, plan, true_shape, grid,
                                        batch=batch)
        scratch = [
            vmem_scratch(buf_shape, padded.dtype),
            vmem_scratch(buf_shape, padded.dtype),
            dma_semaphore,
            dma_semaphore,
        ]
    else:
        kernel = build_superstep_kernel(program, plan, true_shape,
                                        batch=batch)
        scratch = [
            vmem_scratch(buf_shape, padded.dtype),
            dma_semaphore,
        ]

    vgrid = grid if batch is None else (batch,) + grid
    out_shape = rounded if batch is None else (batch,) + rounded
    out_block = block if batch is None else (1,) + block

    out = pl.pallas_call(
        kernel,
        grid=vgrid,
        in_specs=[
            pl.BlockSpec(memory_space=MemorySpace.SMEM),
            pl.BlockSpec(c2.shape, lambda *g: (0,) * 2),
            pl.BlockSpec(t2.shape, lambda *g: (0,) * 2),
            pl.BlockSpec(memory_space=MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec(out_block, lambda *g: g),
        out_shape=jax.ShapeDtypeStruct(out_shape, padded.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="stencil_superstep_unpadded",
    )(offsets.astype(jnp.int32), c2, t2, padded)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("program", "plan", "true_shape", "interpret",
                     "pipelined", "variant"),
)
def superstep_call(padded: jnp.ndarray, center: jnp.ndarray,
                   taps: jnp.ndarray, program: StencilProgram,
                   plan: BlockPlan, true_shape: Tuple[int, ...],
                   interpret: bool,
                   offsets: jnp.ndarray | None = None,
                   pipelined: bool = False,
                   variant: Optional[str] = None) -> jnp.ndarray:
    """Invoke the pallas kernel over a pre-padded grid.

    ``padded`` has shape ``rounded_up(local) + 2*halo`` per axis — or
    ``(B, ...)`` with a leading batch of independent grids — already
    halo-filled according to the program's boundary mode (pad on a single
    device; neighbor-exchanged + boundary-synthesized under domain
    decomposition).  ``taps`` is the canonical tap-order coefficient vector
    (any leading unit dims are flattened).  ``true_shape`` is the GLOBAL grid
    shape and ``offsets`` this shard's global origin.  Returns the rounded-up
    local grid after ``par_time`` steps; caller slices back.  ``variant``
    supersedes the deprecated ``pipelined`` bool (``None`` defers to it); a
    lone superstep has no chunk to fuse, so "temporal" demotes to plain.
    """
    _note_trace("superstep_call")
    v = normalize_variant(variant, pipelined)
    return _superstep_pallas(padded, center, taps, program, plan, true_shape,
                             interpret, offsets, v == "pipelined")


# ---- padded-carry (zero-copy) fused executor --------------------------------
# The fused run used to re-materialize a boundary_pad copy of the whole grid
# in HBM every superstep — an O(volume) read+write sweep the paper's temporal
# blocking exists to avoid (§III.A).  The machinery below keeps the carry in
# padded layout end-to-end instead: a ping-pong pair of halo-extended buffers,
# the kernel writing its output tile straight into the destination interior,
# and the boundary ring refreshed by O(surface) work only.


@dataclasses.dataclass(frozen=True)
class PaddedLayout:
    """Geometry of the persistent halo-extended carry buffer.

    Each spatial axis is rounded up to a block multiple and extended by the
    plan halo ``H`` on both sides (``padded_shape``).  The superstep kernel
    reads its halo'd window out of one buffer of a ping-pong pair and DMAs
    its output tile straight into the other buffer's interior, so no
    O(volume) re-pad ever materializes between supersteps.

    ``wrap_axes`` lists the axes whose halo ring is refreshed by in-kernel
    periodic wrap copies (device-local periodic axes).  Clamp/constant axes
    leave the ring stale and instead heal each *loaded window* with a t=0
    ``boundary_fixup`` — the border cell is always inside the window, so the
    fixup reproduces ``boundary_pad`` bit-for-bit at O(window-surface) cost.

    ``align`` is the per-axis DMA alignment (:func:`tile_alignment`; empty
    means 1 everywhere): the ring actually allocated on axis d, ``ring[d]``,
    is ``halo`` rounded up to it, so every window and tile a compiled
    kernel moves starts on a register tile.
    """

    halo: int
    local_shape: Tuple[int, ...]
    rounded: Tuple[int, ...]
    wrap_axes: Tuple[int, ...] = ()
    align: Tuple[int, ...] = ()

    @property
    def ring(self) -> Tuple[int, ...]:
        align = self.align or (1,) * len(self.rounded)
        return tuple(round_up(self.halo, a) for a in align)

    @property
    def padded_shape(self) -> Tuple[int, ...]:
        return tuple(r + 2 * g for r, g in zip(self.rounded, self.ring))

    def wrap_degenerate(self) -> bool:
        """True when some wrap axis is too small for the in-kernel refresh.

        The lo ring copies ``ring`` cells out of the true interior and the
        hi region (round-up slack + hi ring) copies ``rounded - n + ring``
        cells; either exceeding the axis extent ``n`` would need multi-lap
        wrap copies, so such configs fall back to the legacy re-pad path.
        """
        ring = self.ring
        for d in self.wrap_axes:
            n = self.local_shape[d]
            if ring[d] > n or self.rounded[d] - n + ring[d] > n:
                return True
        return False


# ---- ring-schedule metadata -------------------------------------------------
# The padded-carry dataflow used to live only inside kernel closures; the
# records below expose the same schedule — wrap/exchange copy geometry, the
# ping-pong alias map, per-superstep windows and write tiles — as inspectable
# data.  The kernels and ``distributed._exchange_into_ring`` consume these
# helpers directly, so ``repro.lint.dataflow``'s abstract interpreter and the
# canary sanitizer analyze the *same* schedule the hardware executes: a
# mutation test that patches ``wrap_copies`` or ``ping_pong_aliases`` mutates
# both the kernel and the model it is checked against.


@dataclasses.dataclass(frozen=True)
class RingCopy:
    """One O(surface) halo copy along ``axis`` in padded coordinates.

    ``kind`` is "wrap" (in-kernel same-buffer periodic refresh) or
    "exchange" (sharded neighbor strip DMA'd into the ring by
    ``distributed._exchange_into_ring``).  ``src``/``dst`` are half-open
    ``[start, stop)`` intervals along ``axis``; all other axes span the
    full padded extent.
    """

    kind: str
    axis: int
    src: Tuple[int, int]
    dst: Tuple[int, int]

    @property
    def width(self) -> int:
        return self.dst[1] - self.dst[0]


def wrap_copies(layout: PaddedLayout) -> Tuple[RingCopy, ...]:
    """The in-kernel periodic refresh schedule for ``layout``.

    Per wrap axis ``d`` (axis-sequential, lo then hi — the order gives
    ``jnp.pad`` wrap corner semantics): the lo ring ``[0, H)`` copies from
    the last ``H`` true cells ``[n, n+H)`` and the hi region ``[H+n, P)``
    (round-up slack plus hi ring, width ``W = P - H - n``) copies from the
    first ``W`` true cells ``[H, H+W)``, with ``H = layout.ring[d]``.
    """
    P = layout.padded_shape
    copies = []
    for d in layout.wrap_axes:
        H = layout.ring[d]
        n = layout.local_shape[d]
        W = P[d] - H - n
        copies.append(RingCopy("wrap", d, (n, n + H), (0, H)))
        copies.append(RingCopy("wrap", d, (H, H + W), (H + n, H + n + W)))
    return tuple(copies)


def exchange_copies(axis: int, h: int, H: int,
                    nloc: int) -> Tuple[RingCopy, RingCopy]:
    """The sharded exchange-into-ring strips along one mesh axis.

    The left neighbor's hi strip ``[H+nloc-h, H+nloc)`` lands just below
    this shard's interior at ``[H-h, H)``; the right neighbor's lo strip
    ``[H, H+h)`` lands just above it at ``[H+nloc, H+nloc+h)``.  ``h`` is
    the *superstep* halo (remainder supersteps exchange shallower strips
    into the same depth-``H`` ring), and the SPMD symmetry makes the src
    intervals this shard's own sends.
    """
    return (
        RingCopy("exchange", axis, (H + nloc - h, H + nloc), (H - h, H)),
        RingCopy("exchange", axis, (H, H + h), (H + nloc, H + nloc + h)),
    )


def ping_pong_aliases(wrap: bool) -> Dict[int, int]:
    """``input_output_aliases`` of one padded superstep launch.

    Operands are ``(offsets, center, taps, src, dst)``.  The tile output
    always donates ``dst`` (input 4); the periodic variant additionally
    returns the ring-refreshed source, donating ``src`` (input 3), because
    the in-kernel wrap refresh mutates that buffer.
    """
    return {3: 0, 4: 1} if wrap else {4: 0}


def tile_output_index(wrap: bool) -> int:
    """Which pallas output carries the advanced interior tiles."""
    return 1 if wrap else 0


@dataclasses.dataclass(frozen=True)
class SuperstepSchedule:
    """One modeled superstep of the padded-carry run.

    ``read_buffer``/``write_buffer`` index the ping-pong pair (0 = the
    buffer holding the initial pad).  ``write_buffer`` is *derived from
    the alias map*: the buffer backing the tile output per
    :func:`ping_pong_aliases` — so a mis-aliased pair shows up here as
    ``write_buffer == read_buffer`` (the RP404 hazard).  ``window_offset``
    is the per-axis ring offset ``ring[d] - h`` at which the cells every
    block's fused steps depend on start (the kernel may DMA a wider,
    tile-aligned frame around them);
    ``ring_deferred`` marks a (buggy) schedule whose ring copies land
    after the dependent window reads.
    """

    index: int
    steps: int
    halo: int
    variant: str
    read_buffer: int
    write_buffer: int
    window_offset: Tuple[int, ...]
    window_shape: Tuple[int, ...]
    write_tile: Tuple[int, ...]
    write_stride: Tuple[int, ...]
    ring: Tuple[RingCopy, ...]
    ring_deferred: bool = False
    fixup: bool = False
    aliases: Tuple[Tuple[int, int], ...] = ()


@dataclasses.dataclass(frozen=True)
class RunSchedule:
    """The inspectable dataflow of one fused padded-carry run.

    ``supersteps`` models the distinct phases a run passes through: up to
    four full supersteps (fresh-pad start plus both steady-state ping-pong
    parities — the buffer-state pattern is 2-periodic, so four entries are
    a fixpoint) and the remainder superstep, if any.  ``fallback`` marks
    wrap-degenerate configs that route through the legacy re-pad body
    (which re-materializes ``boundary_pad`` every superstep and therefore
    has no ring schedule to verify).
    """

    program: StencilProgram
    plan: BlockPlan
    layout: PaddedLayout
    variant: str
    steps: int
    full: int
    rem: int
    supersteps: Tuple[SuperstepSchedule, ...]
    sharded_axes: Tuple[int, ...] = ()
    fallback: bool = False


def ring_schedule(program: StencilProgram, plan: BlockPlan,
                  true_shape: Tuple[int, ...], steps: int, *,
                  variant: Optional[str] = None, pipelined: bool = False,
                  decomp=None, compiled: bool = False) -> RunSchedule:
    """Build the :class:`RunSchedule` that ``run_call`` (or the sharded
    ``run_fn``) executes for this configuration.

    Mirrors the executors' geometry exactly: the chunk-deep ring under
    ``variant="temporal"``, per-device local/rounded shapes under a
    ``decomp`` (axis shard counts or a ``MeshDecomposition``), wrap axes =
    device-local periodic axes, remainder supersteps as one shallower
    plain superstep reading at ring offset ``ring - h``, and — for
    ``compiled`` kernels — the ring rounded up to the register tile.
    """
    v = normalize_variant(variant, pipelined)
    ndim = program.ndim
    chunk = TEMPORAL_CHUNK if v == "temporal" else 1
    H = chunk * plan.halo
    align = tile_alignment(ndim, compiled, program.dtype)
    shards = getattr(decomp, "axis_shards", decomp)
    if shards is not None:
        local = tuple(true_shape[d] // shards[d] for d in range(ndim))
        rounded = local
        wrap_axes = tuple(d for d in range(ndim)
                          if program.boundary == "periodic"
                          and shards[d] == 1)
        sharded_axes = tuple(d for d in range(ndim) if shards[d] > 1)
    else:
        local = tuple(true_shape)
        rounded = tuple(round_up(true_shape[d], plan.block_shape[d])
                        for d in range(ndim))
        wrap_axes = tuple(range(ndim)) \
            if program.boundary == "periodic" else ()
        sharded_axes = ()
    layout = PaddedLayout(halo=H, local_shape=local, rounded=rounded,
                          wrap_axes=wrap_axes, align=align)
    ring = layout.ring
    if shards is None and layout.wrap_degenerate():
        return RunSchedule(program=program, plan=plan, layout=layout,
                           variant=v, steps=steps, full=0, rem=0,
                           supersteps=(), sharded_axes=(), fallback=True)
    period = chunk * plan.par_time
    full, rem = divmod(steps, period)
    wrap = bool(wrap_axes)
    amap = ping_pong_aliases(wrap)
    tout = tile_output_index(wrap)
    # Which operand's buffer backs the tile output?  Input 3 is the window
    # source, input 4 the destination; a tile output aliased onto input 3
    # writes into the buffer the windows read from.
    winput = next((i for i, o in amap.items() if o == tout), 4)
    wraps = wrap_copies(layout)

    def entry(index, rb, ss_steps, ss_variant):
        h = ss_steps * program.halo_radius
        copies = wraps + tuple(
            c for d in sharded_axes
            for c in exchange_copies(d, h, ring[d], local[d]))
        wb = rb if winput == 3 else 1 - rb
        return SuperstepSchedule(
            index=index, steps=ss_steps, halo=h, variant=ss_variant,
            read_buffer=rb, write_buffer=wb,
            window_offset=tuple(g - h for g in ring),
            window_shape=tuple(b + 2 * h for b in plan.block_shape),
            write_tile=tuple(plan.block_shape),
            write_stride=tuple(plan.block_shape),
            ring=copies, fixup=program.boundary != "periodic",
            aliases=tuple(sorted(amap.items())))

    supersteps = []
    rb = 0
    for i in range(min(full, 4)):
        supersteps.append(entry(i, rb, period, v))
        rb = 1 - rb
    if rem:
        supersteps.append(entry(len(supersteps), rb, rem,
                                "plain" if v == "temporal" else v))
    return RunSchedule(program=program, plan=plan, layout=layout, variant=v,
                       steps=steps, full=full, rem=rem,
                       supersteps=tuple(supersteps),
                       sharded_axes=sharded_axes, fallback=False)


def _refresh_wrap_halo(src_ref, layout: PaddedLayout, batch: Optional[int],
                       sem) -> None:
    """In-kernel periodic refresh of the carry's halo ring (same-buffer DMA).

    The copy geometry is :func:`wrap_copies` — axis-sequential with full
    padded extent on the other axes, so corner regions match ``jnp.pad``
    wrap semantics: the lo ring ``[0, H)`` copies from the last ``H`` true
    cells and the hi region ``[H+n, P)`` (round-up slack plus hi ring)
    copies from the first ``P - H - n`` true cells.  O(surface) traffic —
    the only per-superstep cost of a periodic halo.
    """
    ndim = len(layout.rounded)
    P = layout.padded_shape

    def ix(d, start, width):
        win = tuple(pl.ds(0, P[e]) if e != d else pl.ds(start, width)
                    for e in range(ndim))
        if batch is not None:
            win = (pl.ds(0, batch),) + win
        return win

    for c in wrap_copies(layout):
        cp = pltpu.make_async_copy(
            src_ref.at[ix(c.axis, c.src[0], c.src[1] - c.src[0])],
            src_ref.at[ix(c.axis, c.dst[0], c.dst[1] - c.dst[0])], sem)
        cp.start()
        cp.wait()


#: Bytes of VMEM a padded-carry launch may use beyond its frame buffers
#: (strip values, rotated lanes, Mosaic's internal scratch).  The scoped
#: limit passed to the compiler is the frames plus this; the planner's
#: budget (``TpuChip.vmem_budget_bytes``) keeps the sum under the chip.
VMEM_HEADROOM_BYTES = 16 * 1024**2


#: Output vregs (``SUBLANE`` x ``LANE`` tiles) one strip-loop iteration
#: computes at most.  A narrow frame takes taller strips, so the loop's
#: fixed cost per iteration and the multiplies repeated per strip are
#: spread over more vregs; a wide frame (16 or more lane tiles) keeps one
#: row tile a strip, past which the strip's values spill.
STRIP_VREGS = 16


def strip_rows(shape: Tuple[int, ...]) -> int:
    """Rows one strip-loop iteration computes in a frame of ``shape``.

    ``SUBLANE * k``, ``k`` the largest divisor of the frame's row tiles
    with ``k`` times its lane tiles at most :data:`STRIP_VREGS` (at least
    1), so the strips tile the rows exactly.  A frame whose rows are not
    a whole number of row tiles (interpret mode's exact halo) keeps one
    row tile a strip and ends with an overlapping strip.
    """
    rows, lanes = shape[-2], shape[-1]
    if rows % SUBLANE:
        return min(SUBLANE, rows)
    tiles, cols = rows // SUBLANE, -(-lanes // LANE)
    k = max(d for d in range(1, tiles + 1)
            if tiles % d == 0 and (d == 1 or d * cols <= STRIP_VREGS))
    return SUBLANE * k


@dataclasses.dataclass(frozen=True)
class _Frame:
    """One launch's VMEM working frame.

    ``shape`` is the block plus the (tile-rounded) ring on both sides of
    every axis — the exact HBM window the launch DMAs in.  In its buffer
    the frame starts ``guard`` rows down the second-minor axis
    (:func:`repro.core.blocking.frame_buffer_shape`); batched launches keep
    a leading unit axis, addressed by ``lead``.
    """

    shape: Tuple[int, ...]
    guard: int
    batched: bool

    @property
    def lead(self) -> Tuple[int, ...]:
        return (0,) if self.batched else ()

    @property
    def strip(self) -> int:
        return strip_rows(self.shape)

    def buffer_shape(self) -> Tuple[int, ...]:
        s = list(self.shape)
        s[-2] += 2 * self.guard
        return ((1,) if self.batched else ()) + tuple(s)

    def rows(self, plane, r0, pad: int = 0):
        """Load/store index of row strip ``r0`` of a plane, widened by
        ``pad`` rows on both sides (``pad`` is 0 or ``guard``, so a strip
        starting on a tile row stays tile-aligned)."""
        return self.lead + tuple(plane) + (
            pl.ds(self.guard + r0 - pad, self.strip + 2 * pad), slice(None))

    def region(self, starts, sizes):
        """DMA view of a box of the frame (frame coordinates)."""
        idx = [pl.ds(s, n) for s, n in zip(starts, sizes)]
        idx[-2] = pl.ds(self.guard + starts[-2], sizes[-2])
        return ((pl.ds(0, 1),) if self.batched else ()) + tuple(idx)


def _for_each_strip(frame: _Frame, body, planes=None) -> None:
    """Call ``body(plane, r0)`` for every row strip of every plane.

    ``plane`` is ``()`` for 2D frames and ``(z,)`` for 3D ones, whose
    leading axis loops over ``planes`` (default: all).  A strip is
    :func:`strip_rows` tall.  Compiled frames have a strip-multiple row
    count, so every ``r0`` is a tile row; other (interpret-mode) frames end
    with an overlapping strip, which recomputes rows it already holds.
    """
    rows = frame.shape[-2]
    S = frame.strip
    nstrips = -(-rows // S)
    aligned = rows % S == 0

    def strips(plane):
        def one(i, carry):
            r0 = pl.multiple_of(i * S, S) if aligned \
                else jnp.minimum(i * S, rows - S)
            body(plane, r0)
            return carry
        lax.fori_loop(0, nstrips, one, 0)

    if len(frame.shape) == 2:
        strips(())
        return
    lo, hi = planes if planes is not None else (0, frame.shape[0])

    def plane_body(z, carry):
        strips((z,))
        return carry
    lax.fori_loop(lo, hi, plane_body, 0)


def _apply_step(program: StencilProgram, center, taps, src, dst,
                frame: _Frame) -> None:
    """One stencil application over the whole frame, ``src`` -> ``dst``.

    Same arithmetic as :func:`tap_interior_update` (canonical tap order,
    no reassociation), computed one row strip at a time.  Per plane offset
    the strip is loaded once — widened by the guard rows when some tap
    moves along the second-minor axis, whose offsets are then static
    sublane slices — and a lane offset is a lane rotation.  Cells within
    ``halo_radius`` of the frame edge receive garbage (guard rows,
    rotated-in lanes, unwritten planes); the overlapped blocking invariant
    ``ring >= steps * halo_radius`` keeps it out of the block.
    """
    r = program.halo_radius
    S, lanes, g = frame.strip, frame.shape[-1], frame.guard
    zero = (0,) * program.ndim
    row_keys = {off[:-2] for off in program.neighbor_taps if off[-2]}

    def body(plane, r0):
        loads = {}

        def tap(off):
            key = off[:-2]
            if key not in loads:
                at = tuple(p + o for p, o in zip(plane, key))
                pad = g if key in row_keys else 0
                loads[key] = (pad, src[frame.rows(at, r0, pad)])
            pad, x = loads[key]
            if pad:
                x = x[pad + off[-2]:pad + off[-2] + S]
            return x if off[-1] == 0 else pltpu.roll(x, (-off[-1]) % lanes, 1)

        acc = center * tap(zero)
        for k, off in enumerate(program.neighbor_taps):
            acc = acc + taps[k] * tap(off)
        dst[frame.rows(plane, r0)] = acc

    _for_each_strip(frame, body,
                    planes=(r, frame.shape[0] - r) if program.ndim == 3
                    else None)


def _fixup_frame(program: StencilProgram, buf, frame: _Frame, starts,
                 true_shape: Tuple[int, ...]) -> None:
    """Restore boundary semantics on the frame's out-of-grid cells.

    The in-VMEM form of :func:`boundary_fixup`, in one strip pass and only
    for launches whose frame crosses the grid boundary.  ``starts[d]`` is
    the global coordinate of frame cell 0 on axis d.  Constant fills every
    out-of-grid cell.  Clamp loads each strip from its clamped plane,
    replaces out-of-grid rows by the border rows of that plane and then
    out-of-grid lanes by each row's border lane (reduced out of the strip),
    so corners take the border value of every axis.  Only out-of-grid
    cells are written and only in-grid cells are read, so the in-place
    pass is order-independent.
    """
    if program.boundary == "periodic":
        return
    nd = program.ndim
    shape = frame.shape
    S, lanes = frame.strip, shape[-1]
    crosses = (starts[0] < 0) | (starts[0] + shape[0] > true_shape[0])
    for d in range(1, nd):
        crosses = crosses | (starts[d] < 0) \
            | (starts[d] + shape[d] > true_shape[d])

    def border(d):
        n = true_shape[d]
        return (jnp.clip(-starts[d], 0, shape[d] - 1),
                jnp.clip(n - 1 - starts[d], 0, shape[d] - 1))

    def body(plane, r0):
        rows, cols = nd - 2, nd - 1
        rp = starts[rows] + r0 + lax.broadcasted_iota(
            jnp.int32, (S, lanes), 0)
        lp = starts[cols] + lax.broadcasted_iota(jnp.int32, (S, lanes), 1)
        if program.boundary == "constant":
            x = buf[frame.rows(plane, r0)]
            out = (rp < 0) | (rp > true_shape[rows] - 1) \
                | (lp < 0) | (lp > true_shape[cols] - 1)
            if plane:
                zp = starts[0] + plane[0]
                out = out | (zp < 0) | (zp > true_shape[0] - 1)
            buf[frame.rows(plane, r0)] = jnp.where(
                out, jnp.asarray(program.boundary_value, x.dtype), x)
            return
        src = (jnp.clip(plane[0], *border(0)),) if plane else ()
        x = buf[frame.rows(src, r0)]
        first, last = border(rows)
        lo = buf[frame.lead + src + (pl.ds(frame.guard + first, 1),
                                     slice(None))]
        hi = buf[frame.lead + src + (pl.ds(frame.guard + last, 1),
                                     slice(None))]
        x = jnp.where(rp < 0, lo, x)
        x = jnp.where(rp > true_shape[rows] - 1, hi, x)
        zero = jnp.zeros_like(x)
        lo = jnp.sum(jnp.where(lp == 0, x, zero), axis=1, keepdims=True)
        hi = jnp.sum(jnp.where(lp == true_shape[cols] - 1, x, zero), axis=1,
                     keepdims=True)
        x = jnp.where(lp < 0, lo, x)
        buf[frame.rows(plane, r0)] = jnp.where(
            lp > true_shape[cols] - 1, hi, x)

    @pl.when(crosses)
    def _fix():
        _for_each_strip(frame, body)


def _fused_steps_vmem(program: StencilProgram, steps: int, center, taps,
                      buf, work, frame: _Frame, starts,
                      true_shape: Tuple[int, ...]):
    """Heal the loaded frame, then run ``steps`` stencil applications
    ping-ponging between ``buf`` and ``work``; returns the ref holding the
    result (boundary semantics restored between steps, as in
    :func:`_fused_steps`)."""
    _fixup_frame(program, buf, frame, starts, true_shape)
    cur, nxt = buf, work
    for t in range(1, steps + 1):
        _apply_step(program, center, taps, cur, nxt, frame)
        cur, nxt = nxt, cur
        if t < steps:
            _fixup_frame(program, cur, frame, starts, true_shape)
    return cur


def _coeff_scalars(c_ref, t_ref, ntaps: int, dtype):
    """The stencil coefficients, read out of SMEM as scalars."""
    return (c_ref[0].astype(dtype),
            [t_ref[k].astype(dtype) for k in range(ntaps)])


def _launch_geometry(program: StencilProgram, plan: BlockPlan,
                     layout: PaddedLayout, batch: Optional[int]):
    """(frame, ring) of one padded-carry launch."""
    ring = layout.ring
    align = layout.align or (1,) * len(ring)
    if any(b % a for b, a in zip(plan.block_shape, align)):
        raise ValueError(
            f"block {plan.block_shape} is not a multiple of the register "
            f"tile {align} that compiled kernels DMA by; plan with the "
            f"compiled backend (plan='auto' or 'model') or round the block")
    shape = tuple(b + 2 * g for b, g in zip(plan.block_shape, ring))
    return _Frame(shape, guard_rows(program.halo_radius),
                  batch is not None), ring


def build_padded_superstep_kernel(program: StencilProgram, plan: BlockPlan,
                                  layout: PaddedLayout,
                                  global_shape: Tuple[int, ...],
                                  batch: Optional[int] = None):
    """Kernel body for one superstep over the persistent padded carry.

    DMAs the block's frame (block + ring per axis, tile-aligned on compiled
    backends) out of the padded source buffer, heals the stale boundary
    ring with a t=0 fixup, runs the fused steps between two VMEM frames,
    and DMAs the block interior into the destination buffer.  A shallower
    remainder superstep reuses the same frame: its steps only need the
    inner ``plan.halo`` cells of the ring.  With ``layout.wrap_axes`` the
    first grid iteration refreshes the periodic ring in place first — the
    source buffer is then also an aliased output (see
    ``_padded_superstep_pallas``).
    """
    ndim = program.ndim
    block = plan.block_shape
    frame, ring = _launch_geometry(program, plan, layout, batch)
    wrap = bool(layout.wrap_axes)
    ntaps = program.num_neighbor_taps

    def _body(offs_ref, c_ref, t_ref, src_ref, o_ref, buf, work, sem_in,
              sem_out, sem_wrap):
        if batch is None:
            pids = [pl.program_id(d) for d in range(ndim)]
            hb = ()
        else:
            pids = [pl.program_id(d + 1) for d in range(ndim)]
            hb = (pl.ds(pl.program_id(0), 1),)
        if wrap:
            first = pids[0] == 0
            for d in range(1, ndim):
                first = first & (pids[d] == 0)
            if batch is not None:
                first = first & (pl.program_id(0) == 0)

            @pl.when(first)
            def _wrap():
                _refresh_wrap_halo(src_ref, layout, batch, sem_wrap)

        win = hb + tuple(pl.ds(pids[d] * block[d], frame.shape[d])
                         for d in range(ndim))
        cp = pltpu.make_async_copy(
            src_ref.at[win], buf.at[frame.region((0,) * ndim, frame.shape)],
            sem_in)
        cp.start()
        cp.wait()

        center, taps = _coeff_scalars(c_ref, t_ref, ntaps, buf.dtype)
        starts = tuple(offs_ref[d] + pids[d] * block[d] - ring[d]
                       for d in range(ndim))
        res = _fused_steps_vmem(program, plan.par_time, center, taps, buf,
                                work, frame, starts, global_shape)
        win_out = hb + tuple(pl.ds(ring[d] + pids[d] * block[d], block[d])
                             for d in range(ndim))
        cpo = pltpu.make_async_copy(res.at[frame.region(ring, block)],
                                    o_ref.at[win_out], sem_out)
        cpo.start()
        cpo.wait()

    if wrap:
        def kernel(offs_ref, c_ref, t_ref, src_in, dst_in, src_ref, o_ref,
                   buf, work, sem_in, sem_out, sem_wrap):
            del src_in, dst_in
            _body(offs_ref, c_ref, t_ref, src_ref, o_ref, buf, work,
                  sem_in, sem_out, sem_wrap)
    else:
        def kernel(offs_ref, c_ref, t_ref, src_ref, dst_in, o_ref, buf,
                   work, sem_in, sem_out):
            del dst_in
            _body(offs_ref, c_ref, t_ref, src_ref, o_ref, buf, work,
                  sem_in, sem_out, None)
    return kernel


def build_padded_pipelined_kernel(program: StencilProgram, plan: BlockPlan,
                                  layout: PaddedLayout,
                                  global_shape: Tuple[int, ...],
                                  grid: Tuple[int, ...],
                                  batch: Optional[int] = None):
    """Double-buffered padded-carry variant of the superstep kernel.

    Same prefetch schedule as :func:`build_pipelined_kernel` (block g+1's
    frame DMA issued before block g's compute, frame buffers alternating
    by linearized parity), lifted onto the persistent padded carry: the
    fused steps ping-pong between the current frame and a shared work
    frame, and the block interior is DMA'd into the destination.  The
    periodic wrap refresh runs once, before the very first prefetch, so
    every streamed frame already sees a fresh ring.
    """
    ndim = program.ndim
    block = plan.block_shape
    frame, ring = _launch_geometry(program, plan, layout, batch)
    wrap = bool(layout.wrap_axes)
    ntaps = program.num_neighbor_taps
    vgrid = grid if batch is None else (batch,) + tuple(grid)
    nd_all = len(vgrid)
    total = math.prod(vgrid)

    def _coords(lin):
        idx = []
        rem = lin
        for d in range(nd_all - 1, -1, -1):
            idx.append(rem % vgrid[d])
            rem = rem // vgrid[d]
        return tuple(reversed(idx))

    def _body(offs_ref, c_ref, t_ref, src_ref, o_ref, buf0, buf1, work,
              sem0, sem1, sem_out, sem_wrap):
        ids = [pl.program_id(d) for d in range(nd_all)]
        lin = ids[0]
        for d in range(1, nd_all):
            lin = lin * vgrid[d] + ids[d]
        parity = jax.lax.rem(lin, 2)
        pids = ids if batch is None else ids[1:]
        hb = () if batch is None else (pl.ds(ids[0], 1),)

        if wrap:
            @pl.when(lin == 0)
            def _wrap():
                _refresh_wrap_halo(src_ref, layout, batch, sem_wrap)

        def _copy(lin_idx, buf, sem):
            coords = _coords(lin_idx)
            sp = coords if batch is None else coords[1:]
            win = tuple(pl.ds(sp[d] * block[d], frame.shape[d])
                        for d in range(ndim))
            if batch is not None:
                win = (pl.ds(coords[0], 1),) + win
            return pltpu.make_async_copy(
                src_ref.at[win],
                buf.at[frame.region((0,) * ndim, frame.shape)], sem)

        @pl.when(lin == 0)
        def _prologue():
            _copy(lin, buf0, sem0).start()

        nxt = lin + 1

        @pl.when((nxt < total) & (parity == 0))
        def _prefetch_odd():
            _copy(nxt, buf1, sem1).start()

        @pl.when((nxt < total) & (parity == 1))
        def _prefetch_even():
            _copy(nxt, buf0, sem0).start()

        center, taps = _coeff_scalars(c_ref, t_ref, ntaps, work.dtype)
        starts = tuple(offs_ref[d] + pids[d] * block[d] - ring[d]
                       for d in range(ndim))
        win_out = hb + tuple(pl.ds(ring[d] + pids[d] * block[d], block[d])
                             for d in range(ndim))

        def _compute(buf, sem):
            _copy(lin, buf, sem).wait()
            res = _fused_steps_vmem(program, plan.par_time, center, taps,
                                    buf, work, frame, starts, global_shape)
            cpo = pltpu.make_async_copy(res.at[frame.region(ring, block)],
                                        o_ref.at[win_out], sem_out)
            cpo.start()
            cpo.wait()

        @pl.when(parity == 0)
        def _run_even():
            _compute(buf0, sem0)

        @pl.when(parity == 1)
        def _run_odd():
            _compute(buf1, sem1)

    if wrap:
        def kernel(offs_ref, c_ref, t_ref, src_in, dst_in, src_ref, o_ref,
                   buf0, buf1, work, sem0, sem1, sem_out, sem_wrap):
            del src_in, dst_in
            _body(offs_ref, c_ref, t_ref, src_ref, o_ref, buf0, buf1,
                  work, sem0, sem1, sem_out, sem_wrap)
    else:
        def kernel(offs_ref, c_ref, t_ref, src_ref, dst_in, o_ref, buf0,
                   buf1, work, sem0, sem1, sem_out):
            del dst_in
            _body(offs_ref, c_ref, t_ref, src_ref, o_ref, buf0, buf1,
                  work, sem0, sem1, sem_out, None)
    return kernel


def build_temporal_kernel(program: StencilProgram, plan: BlockPlan,
                          layout: PaddedLayout,
                          global_shape: Tuple[int, ...],
                          batch: Optional[int] = None,
                          chunk: int = TEMPORAL_CHUNK):
    """Superstep-chunk kernel: ``chunk`` supersteps fused into ONE launch.

    Overlapped tiling in time, lifted one level above the per-superstep
    fusion: the launch DMAs a chunk-deep frame (``block + 2 * chunk *
    plan.halo`` per axis, tile-rounded when compiled) out of the padded
    carry, applies ``chunk * plan.par_time`` stencil applications — each
    inner step consumes ``halo_radius`` cells of the overlap (paper eq. 2)
    — and writes only the final block interior back.  The carry ping-pong
    and the per-block frame stream are thus paid once per ``chunk``
    supersteps, dropping per-superstep HBM traffic to ~1/chunk of the plain
    kernel's (``BlockPlan.run_bytes_per_superstep`` with
    ``variant="temporal"`` is the model; the traffic guard in
    tests/test_temporal_variant.py measures it).

    Structurally this IS :func:`build_padded_superstep_kernel` built for the
    chunk-deep plan (``par_time * chunk``): the step loop, per-step boundary
    fixup, frame reuse, and wrap refresh are all shared, so the temporal
    variant inherits the plain path's proven boundary semantics — only the
    traffic accounting changes.  ``layout`` must carry the chunk-deep ring
    (``layout.halo >= chunk * plan.halo``).
    """
    deep = dataclasses.replace(plan, par_time=plan.par_time * chunk)
    return build_padded_superstep_kernel(program, deep, layout, global_shape,
                                         batch=batch)


def _padded_superstep_pallas(src: jnp.ndarray, dst: jnp.ndarray,
                             center: jnp.ndarray, taps: jnp.ndarray, *,
                             program: StencilProgram, plan: BlockPlan,
                             layout: PaddedLayout,
                             global_shape: Tuple[int, ...],
                             interpret: bool,
                             role: str = "superstep",
                             offsets: jnp.ndarray | None = None,
                             pipelined: bool = False,
                             variant: Optional[str] = None):
    """One superstep (or, for ``variant="temporal"``, one superstep-chunk
    advancing ``TEMPORAL_CHUNK`` supersteps) over the persistent padded
    carry.

    ``role`` is the launch's place in the run, ``"superstep"`` (a full
    superstep or chunk) or ``"remainder"``; the kernel is named
    ``stencil_<role>_<variant>`` in the compiled program and its traces.

    ``src`` and ``dst`` are both in padded layout (``layout.padded_shape``
    per spatial axis, optionally behind one batch axis).  Returns
    ``(src', out)``: ``out`` holds the advanced grid in its interior (built
    in ``dst``'s donated buffer via ``input_output_aliases``) and ``src'``
    is the — for periodic, ring-refreshed — source, ready to become the
    next superstep's destination.  Only the periodic variant aliases the
    source as a second output (its ring refresh mutates the buffer);
    clamp/constant leave ``src`` a plain input so the executable carries a
    single P-sized output.  ``variant`` supersedes the deprecated
    ``pipelined`` bool (``None`` defers to it).  The coefficients ride in
    SMEM; the compiler's scoped-VMEM limit is the launch's frame buffers
    plus :data:`VMEM_HEADROOM_BYTES`.
    """
    v = normalize_variant(variant, pipelined)
    ndim = program.ndim
    batch: Optional[int] = src.shape[0] \
        if batch_dims(program, src.ndim) else None
    block = plan.block_shape
    grid = tuple(layout.rounded[d] // block[d] for d in range(ndim))
    wrap = bool(layout.wrap_axes)

    if offsets is None:
        offsets = jnp.zeros((ndim,), jnp.int32)
    c1 = center.reshape((1,)).astype(jnp.float32)
    t1 = taps.reshape((-1,)).astype(jnp.float32)

    frame, _ = _launch_geometry(program, plan, layout, batch)
    frame_buf = vmem_scratch(frame.buffer_shape(), src.dtype)
    if v == "pipelined":
        kernel = build_padded_pipelined_kernel(program, plan, layout,
                                               global_shape, grid,
                                               batch=batch)
        scratch = [frame_buf, frame_buf, frame_buf,
                   dma_semaphore, dma_semaphore, dma_semaphore]
    else:
        if v == "temporal":
            kernel = build_temporal_kernel(program, plan, layout,
                                           global_shape, batch=batch)
        else:
            kernel = build_padded_superstep_kernel(program, plan, layout,
                                                   global_shape, batch=batch)
        scratch = [frame_buf, frame_buf, dma_semaphore, dma_semaphore]
    if wrap:
        scratch.append(dma_semaphore)
    frames = 3 if v == "pipelined" else 2
    vmem_limit = frames * math.prod(frame.buffer_shape()) \
        * jnp.dtype(src.dtype).itemsize + VMEM_HEADROOM_BYTES

    vgrid = grid if batch is None else (batch,) + grid
    in_specs = [
        pl.BlockSpec(memory_space=MemorySpace.SMEM),
        pl.BlockSpec(memory_space=MemorySpace.SMEM),
        pl.BlockSpec(memory_space=MemorySpace.SMEM),
        pl.BlockSpec(memory_space=MemorySpace.ANY),
        pl.BlockSpec(memory_space=MemorySpace.ANY),
    ]
    struct = jax.ShapeDtypeStruct(src.shape, src.dtype)
    any_spec = pl.BlockSpec(memory_space=MemorySpace.ANY)
    out = pl.pallas_call(
        kernel,
        grid=vgrid,
        in_specs=in_specs,
        out_specs=[any_spec, any_spec] if wrap else any_spec,
        out_shape=[struct, struct] if wrap else struct,
        scratch_shapes=scratch,
        input_output_aliases=dict(ping_pong_aliases(wrap)),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name=f"stencil_{role}_{v}",
    )(offsets.astype(jnp.int32), c1, t1, src, dst)
    if wrap:
        return out[0], out[1]
    return src, out


def _run_call_padfallback(grid: jnp.ndarray, center: jnp.ndarray,
                          taps: jnp.ndarray, full: jnp.ndarray, *,
                          program: StencilProgram, plan: BlockPlan,
                          true_shape: Tuple[int, ...], interpret: bool,
                          rem: int, pipelined: bool = False,
                          variant: Optional[str] = None) -> jnp.ndarray:
    """Legacy fused-run body: re-pad the true region every superstep.

    Kept only for wrap-degenerate periodic configs (a wrap axis smaller
    than the layout halo or the round-up slack — see
    ``PaddedLayout.wrap_degenerate``), where the in-kernel ring refresh
    would need multi-lap copies.  Costs an O(volume) extra sweep per
    superstep; every other config takes the padded-carry path.

    ``variant`` supersedes the deprecated ``pipelined`` bool and must be
    "plain" or "pipelined": a wrap-degenerate temporal run is lowered by
    ``run_call`` as the chunk-deep *plan* with the plain kernel, so this
    body never builds a temporal window itself.
    """
    v = normalize_variant(variant, pipelined)
    if v == "temporal":
        raise ValueError(
            "pass the chunk-deep plan with variant='plain' instead of "
            "variant='temporal' to _run_call_padfallback")
    pipe = v == "pipelined"
    ndim = program.ndim
    nb = grid.ndim - ndim
    rounded = tuple(round_up(true_shape[d], plan.block_shape[d])
                    for d in range(ndim))
    g = jnp.pad(grid, [(0, 0)] * nb + [
        (0, rounded[d] - true_shape[d]) for d in range(ndim)])
    true_ix = (slice(None),) * nb + tuple(
        slice(0, true_shape[d]) for d in range(ndim))

    def superstep(g, step_plan):
        h = step_plan.halo
        pad = [(0, 0)] * nb + [
            (h, rounded[d] - true_shape[d] + h) for d in range(ndim)]
        padded = boundary_pad(program, g[true_ix], pad)
        return _superstep_pallas(padded, center, taps, program, step_plan,
                                 true_shape, interpret, None, pipe)

    g = lax.fori_loop(0, full, lambda _, g: superstep(g, plan), g)
    if rem:
        g = superstep(g, dataclasses.replace(plan, par_time=rem))
    return g[true_ix]


@functools.partial(
    jax.jit,
    static_argnames=("program", "plan", "true_shape", "interpret", "rem",
                     "pipelined", "variant"),
    donate_argnums=(0,),
)
def run_call(grid: jnp.ndarray, center: jnp.ndarray,
             taps: jnp.ndarray, full: jnp.ndarray, *,
             program: StencilProgram, plan: BlockPlan,
             true_shape: Tuple[int, ...], interpret: bool, rem: int,
             pipelined: bool = False,
             variant: Optional[str] = None) -> jnp.ndarray:
    """Fused multi-superstep executor over a persistent padded carry.

    ``grid`` is the true-shaped grid (``(B, *true_shape)`` with a leading
    batch of independent grids); its buffer is **donated**.  On entry it is
    padded ONCE into halo-extended layout (:class:`PaddedLayout`); every
    superstep then ping-pongs between two padded buffers — the kernel reads
    its halo'd window from one and DMAs the output tile into the other's
    interior, with the boundary ring healed by O(surface) work (in-kernel
    wrap copies for periodic; per-window t=0 fixup for clamp/constant)
    instead of the historical O(volume) re-pad.  Per-superstep HBM traffic
    is therefore the kernel's own stream (overlapping halo'd reads + tile
    writes) plus the ping-pong pass-through, matching
    ``BlockPlan.run_bytes_per_superstep``.

    ``variant`` selects the kernel variant ("plain" | "pipelined" |
    "temporal"; ``None`` defers to the deprecated ``pipelined`` bool).
    Under ``variant="temporal"`` the carry ring is ``TEMPORAL_CHUNK`` times
    deeper and each loop iteration is one superstep-*chunk*
    (:func:`build_temporal_kernel` advancing ``TEMPORAL_CHUNK * par_time``
    steps per launch); ``full`` then counts chunks and ``rem`` leftover
    *steps* in ``[0, TEMPORAL_CHUNK * par_time)``, executed as one plain
    shallower superstep reading inside the same deep ring (the existing
    ring-offset reuse).  Wrap-degenerate periodic configs fall back to the
    legacy re-pad body, for temporal with the chunk-deep plan so the step
    count is preserved.

    ``full`` is the number of full supersteps (chunks) and stays *dynamic*
    (a ``fori_loop`` trip count): any ``steps = k * period + rem`` with the
    same remainder reuses one executable; only a distinct ``rem`` (a
    shallower remainder superstep reading inside the same ring)
    recompiles.  Returns the true-shaped grid after ``full * period + rem``
    steps — the interior slice of the final carry.
    """
    _note_trace("run_call")
    v = normalize_variant(variant, pipelined)
    ndim = program.ndim
    nb = grid.ndim - ndim
    chunk = TEMPORAL_CHUNK if v == "temporal" else 1
    H = chunk * plan.halo
    rounded = tuple(round_up(true_shape[d], plan.block_shape[d])
                    for d in range(ndim))
    wrap_axes = tuple(range(ndim)) if program.boundary == "periodic" else ()
    layout = PaddedLayout(halo=H, local_shape=tuple(true_shape),
                          rounded=rounded, wrap_axes=wrap_axes,
                          align=tile_alignment(ndim, not interpret,
                                               program.dtype))
    if layout.wrap_degenerate():
        fb_plan = plan if v != "temporal" else dataclasses.replace(
            plan, par_time=plan.par_time * TEMPORAL_CHUNK)
        return _run_call_padfallback(grid, center, taps, full,
                                     program=program, plan=fb_plan,
                                     true_shape=true_shape,
                                     interpret=interpret, rem=rem,
                                     variant="plain" if v == "temporal"
                                     else v)
    P, ring = layout.padded_shape, layout.ring
    src = jnp.pad(grid, [(0, 0)] * nb + [
        (ring[d], P[d] - ring[d] - true_shape[d]) for d in range(ndim)])
    dst = jnp.zeros_like(src)

    def superstep(carry, step_plan, step_variant, role="superstep"):
        s, d = carry
        s2, o = _padded_superstep_pallas(
            s, d, center, taps, program=program, plan=step_plan,
            layout=layout, global_shape=tuple(true_shape),
            interpret=interpret, role=role, variant=step_variant)
        return (o, s2)

    interior = (slice(None),) * nb + tuple(
        slice(ring[d], ring[d] + true_shape[d]) for d in range(ndim))

    def finish(carry):
        if rem:
            # The remainder (< chunk * par_time steps) runs as one plain (or
            # pipelined) shallower superstep: its steps depend only on the
            # inner rem * halo_radius cells of the same deep ring.
            carry = superstep(carry, dataclasses.replace(plan, par_time=rem),
                              "plain" if v == "temporal" else v,
                              role="remainder")
        return carry[0][interior]

    # Two supersteps per trip hand the ping-pong pair back to its own loop
    # slots; a loop body that swaps them makes XLA copy both padded buffers
    # on every trip.  An odd count runs its last full superstep in the tail.
    carry = lax.fori_loop(
        0, full // 2,
        lambda _, c: superstep(superstep(c, plan, v), plan, v), (src, dst))
    return lax.cond(full % 2 == 1,
                    lambda c: finish(superstep(c, plan, v)), finish, carry)
