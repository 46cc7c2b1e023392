"""RP4xx symbolic half: abstract interpretation of the padded ring schedule.

The padded-carry executor (``kernels.common.run_call``, the sharded
``distributed.run_fn``) never re-materializes a boundary pad; correctness
instead rests on a hand-scheduled dataflow — ping-pong donated buffers,
in-kernel wrap DMAs, exchange-into-ring strips, ring-offset window reuse
for remainder supersteps, and the temporal chunk's shrinking valid
regions.  :func:`verify_dataflow` proves that schedule sound for one
(program, plan, grid, variant, steps[, decomp]) configuration by
interpreting :func:`repro.kernels.common.ring_schedule` — the *same*
metadata the kernels are built from — over a per-axis timestamp lattice:

* every cell a block window reads must be initialized *at the current
  superstep's time* by the initial pad, a prior superstep's write, a wrap
  or exchange ring copy, or (for out-of-grid positions under
  clamp/constant) the kernel's t=0 ``boundary_fixup``  — else **RP401**
  (or **RP405** when the failure is a periodic wrap copy that is missing
  or ordered after the dependent read);
* the output tiles must write every interior cell exactly once per
  superstep — **RP402** for coverage holes, **RP403** for overlaps or
  out-of-interior writes;
* the ping-pong alias map must route the tile output into the
  destination buffer, never the window source — **RP404**.

Axes are independent under the axis-sequential ring schedule (wrap
copies span the full padded extent of the other axes, windows are
Cartesian products), so the interpreter runs per axis on 1-D integer
arrays — pure numpy, well under the 2 ms pre-flight budget guarded in
tests/test_dataflow.py.

The dynamic oracle validating this model is ``repro.lint.sanitize``:
mutation tests seed the same schedule bugs into both halves (they share
``wrap_copies``/``ping_pong_aliases``) and require the same RP4xx code
from each.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from repro.core.blocking import BlockPlan
from repro.core.program import as_program
from repro.lint.diagnostics import Diagnostic, error, raise_on_error

#: Timestamp marking a cell no pad, write, or ring copy ever initialized.
STALE = -1


def verify_dataflow(program, plan: BlockPlan, grid_shape, *,
                    steps: int, variant: Optional[str] = None,
                    decomp=None, schedule=None,
                    compiled: bool = False) -> List[Diagnostic]:
    """Prove the padded ring schedule of one run configuration correct.

    Returns every RP4xx finding (empty list == the schedule is sound).
    ``schedule`` overrides the derived :class:`~repro.kernels.common.
    RunSchedule` — the hook mutation tests use to seed schedule-level
    bugs; normal callers leave it ``None``.  ``decomp`` takes per-axis
    shard counts or a ``MeshDecomposition``; sharded exchange strips are
    modeled via SPMD symmetry (every shard sees the identical state
    pattern, so a neighbor's strip carries this shard's own timestamps).
    ``compiled`` verifies the compiled kernels' tile-rounded ring.
    """
    from repro.kernels import common

    prog = as_program(program)
    if schedule is None:
        schedule = common.ring_schedule(prog, plan, tuple(grid_shape),
                                        int(steps), variant=variant,
                                        decomp=decomp, compiled=compiled)
    if schedule.fallback or not schedule.supersteps:
        # The wrap-degenerate re-pad fallback re-materializes boundary_pad
        # every superstep — no ring schedule exists to verify (RP108
        # already warns about the O(volume) cost).
        return []

    out: List[Diagnostic] = []
    for ss in schedule.supersteps:
        if ss.write_buffer == ss.read_buffer:
            out.append(error(
                "RP404",
                f"superstep {ss.index}: input_output_aliases "
                f"{dict(ss.aliases)} route the tile output into buffer "
                f"{ss.read_buffer} — the buffer the halo'd windows read "
                f"from — so blocks written early are read back, already "
                f"overwritten, by later windows",
                hint="alias the tile output onto the destination operand "
                     "(input 4), and the refreshed source onto input 3"))
    for d in range(prog.ndim):
        out.extend(_verify_axis(schedule, prog, plan, d))
    return out


def check_dataflow(program, plan: BlockPlan, grid_shape, *,
                   steps: int, variant: Optional[str] = None,
                   decomp=None, schedule=None) -> List[Diagnostic]:
    """:func:`verify_dataflow`, raising :class:`DiagnosticError` on errors."""
    return raise_on_error(
        verify_dataflow(program, plan, grid_shape, steps=steps,
                        variant=variant, decomp=decomp, schedule=schedule),
        source="dataflow")


def _apply_copy(vec: np.ndarray, copy) -> None:
    """Apply one ring copy's timestamp transfer along this axis."""
    s0, s1 = copy.src
    d0, d1 = copy.dst
    w = min(s1 - s0, d1 - d0)
    if w <= 0:
        return
    P = vec.shape[0]
    # Clip to the buffer so a seeded out-of-range mutation degrades to a
    # partial (detectably stale) refresh instead of crashing the model.
    if s0 < 0 or d0 < 0 or s0 + w > P or d0 + w > P:
        lo = max(0, -min(s0, d0))
        w = min(w, P - max(s0, d0)) - lo
        s0, d0 = s0 + lo, d0 + lo
        if w <= 0:
            return
    vec[d0:d0 + w] = vec[s0:s0 + w]


def _verify_axis(sched, prog, plan: BlockPlan, d: int) -> List[Diagnostic]:
    layout = sched.layout
    H = layout.ring[d]
    P = layout.padded_shape[d]
    n = layout.local_shape[d]
    R = layout.rounded[d]
    b = plan.block_shape[d]
    nblocks = R // b
    r = prog.halo_radius
    wrap_axis = d in layout.wrap_axes
    sharded = d in sched.sharded_axes
    out: List[Diagnostic] = []

    # state[buf][cell] = superstep-time the cell's value corresponds to,
    # or STALE.  Buffer 0 starts holding the zero-padded true interior at
    # time 0; everything else (both rings, the round-up slack, all of
    # buffer 1) is uninitialized.
    state = np.full((2, P), STALE, dtype=np.int64)
    state[0, H:H + n] = 0
    tau = 0

    for ss in sched.supersteps:
        rb = ss.read_buffer
        # A mis-aliased superstep (RP404, already reported structurally)
        # is modeled as if it wrote the intended destination so the
        # remaining supersteps stay analyzable.
        wb = 1 - rb if ss.write_buffer == rb else ss.write_buffer
        ring_here = [c for c in ss.ring if c.axis == d]
        missing_wrap = wrap_axis and not any(
            c.kind == "wrap" for c in ring_here)
        late_ring = bool(ss.ring_deferred)
        if not late_ring:
            for c in ring_here:
                _apply_copy(state[rb], c)

        if ss.halo < ss.steps * r:
            out.append(error(
                "RP401",
                f"superstep {ss.index}, axis {d}: halo depth {ss.halo} "
                f"cannot feed {ss.steps} fused steps of radius {r} — "
                f"inner step {ss.halo // r + 1} over-reads past the "
                f"shrinking valid region",
                hint="a superstep advancing s steps needs halo "
                     "s * halo_radius"))

        # Window reads: block i reads [i*b + off, i*b + off + w); the
        # union over i is one contiguous interval (windows overlap).
        off = ss.window_offset[d]
        w = ss.window_shape[d]
        lo = off
        hi = (nblocks - 1) * b + off + w
        if lo < 0 or hi > P:
            out.append(error(
                "RP401",
                f"superstep {ss.index}, axis {d}: block windows span "
                f"[{lo}, {hi}) outside the padded buffer [0, {P})",
                hint="window offset must be layout.ring - plan.halo and "
                     "the window block + 2*halo wide"))
        else:
            cells = np.arange(lo, hi)
            stale = state[rb, lo:hi] != tau
            if ss.fixup and not sharded:
                # boundary_fixup re-derives every out-of-grid position
                # from in-grid data at t=0, so only in-grid cells must be
                # live.  Sharded axes get no such exemption: an interior
                # shard's ring positions are other shards' real interior
                # and must arrive via exchange strips.
                pos = cells - H
                stale &= (pos >= 0) & (pos < n)
            if stale.any():
                cell = int(cells[stale.argmax()])
                code = "RP405" if (wrap_axis and
                                   (missing_wrap or late_ring)) else "RP401"
                why = ("no wrap DMA refreshes the periodic ring before "
                       "the window loads" if code == "RP405" else
                       "the cell was never initialized by pad, prior "
                       "write, ring copy, or boundary_fixup at this time")
                out.append(error(
                    code,
                    f"superstep {ss.index}, axis {d}: window reads stale "
                    f"cell at padded offset {cell} (ring-relative "
                    f"{cell - H}) — {why}",
                    hint="refresh the ring to the superstep halo before "
                         "the first window load"))

        # Interior writes: tile i covers [i*stride, i*stride + tile).
        counts = np.zeros(R, dtype=np.int64)
        oob = False
        for i in range(nblocks):
            ws = i * ss.write_stride[d]
            we = ws + ss.write_tile[d]
            if ws < 0 or we > R:
                oob = True
            counts[max(ws, 0):min(we, R)] += 1
        if oob:
            out.append(error(
                "RP403",
                f"superstep {ss.index}, axis {d}: an output tile writes "
                f"outside the rounded interior [0, {R})",
                hint="tiles must stay inside the destination interior"))
        holes = counts == 0
        if holes.any():
            out.append(error(
                "RP402",
                f"superstep {ss.index}, axis {d}: "
                f"{int(holes.sum())} interior cell(s) never written, "
                f"first at interior offset {int(holes.argmax())}",
                hint="write tiles must tile the rounded interior exactly"))
        overlaps = counts > 1
        if overlaps.any():
            out.append(error(
                "RP403",
                f"superstep {ss.index}, axis {d}: "
                f"{int(overlaps.sum())} interior cell(s) written more "
                f"than once, first at interior offset "
                f"{int(overlaps.argmax())}",
                hint="output tiles never overlap within a superstep"))

        if late_ring:
            for c in ring_here:
                _apply_copy(state[rb], c)
        state[wb, H:H + R][counts > 0] = tau + ss.steps
        tau += ss.steps

    return out
