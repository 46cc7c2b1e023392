"""Model-guided ranking of design-space candidates (paper §V.A).

The paper evaluates its performance model over every feasible configuration
and hands the top of the list to place-and-route; we rank with the TPU
roofline model (``perf_model.predicted_gbps``: bytes streamed + FLOPs
against ``analysis.hw`` chip ceilings, overlap redundancy charged) and hand
the top-K frontier to the empirical harness (``tuning.measure``) — the
model prunes the thousands-point space down to the handful worth timing.

Ordering: predicted effective GB/s descending; ties broken toward
sublane-aligned halos (the paper's eq. 6 preference) and then smaller VMEM
footprints (more headroom for the compiler).

Mesh-aware candidates (``candidate.decomp`` set) are ranked by the
*aggregate* model: per-shard block throughput times the device count, with
the per-superstep ICI halo exchange — ``par_time * halo_radius``-deep
strips ppermute'd both ways along every sharded axis — charged against the
chip's ICI link bandwidth.  Exchange and local compute overlap (XLA's
latency-hiding scheduler; see core/distributed.py), so the superstep takes
``max(compute, exchange)`` — a decomposition whose exchange dominates is
reported ``ici``-bound and ranks accordingly.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

from repro.analysis.hw import TpuChip, V5E
from repro.core import perf_model
from repro.core.blocking import (TEMPORAL_CHUNK, estimate,
                                 grid_useful_fraction, model_order)
from repro.core.program import as_program
from repro.tuning.space import Candidate


@dataclasses.dataclass(frozen=True)
class RankedCandidate:
    candidate: Candidate
    predicted_gbps: float      # effective GB/s (model)
    predicted_gcells: float    # useful GCell/s (model)
    predicted_gflops: float    # useful GFLOP/s (model)
    bound: str                 # "compute" | "memory" | "ici"
    hbm_bytes_per_cell: float = 0.0   # HBM bytes per useful cell-update

    def describe(self) -> str:
        return (f"{self.candidate.describe()} -> "
                f"{self.predicted_gbps:.1f} GB/s "
                f"({self.predicted_gcells:.2f} GCell/s, {self.bound}-bound)")


def exchange_bytes_per_superstep(program, plan, decomp,
                                 grid_shape: Tuple[int, ...]) -> int:
    """ICI bytes one shard moves per superstep: a ``plan.halo``-deep strip
    sent each way along every sharded axis (the deep-halo exchange of
    core/distributed.exchange_halo).  Unsharded axes exchange nothing."""
    prog = as_program(program)
    itemsize = prog.bytes_per_cell // 2     # one array element (Table I
    local = decomp.local_shape(grid_shape)  # counts read + write)
    total = 0
    for d, shards in enumerate(decomp.axis_shards):
        if shards <= 1:
            continue
        strip = plan.halo * math.prod(
            local[e] for e in range(prog.ndim) if e != d)
        total += 2 * strip * itemsize          # both directions
    return total


def predict(program, candidate: Candidate, chip: TpuChip = V5E,
            grid_shape: Optional[Tuple[int, ...]] = None) -> RankedCandidate:
    """Model prediction for one candidate (grid-padding waste charged when
    the target grid is known — same penalty ``blocking.plan_blocking``
    applies).  Decomposed candidates get the aggregate mesh model with the
    exchange traffic charged (see module docstring).  Every branch charges
    the frames of the candidate's backend (``Candidate.compiled``): a
    compiled launch reads and sweeps its whole tile-rounded frame at every
    fused step (``BlockPlan.cells_per_block``)."""
    prog = as_program(program)
    variant = candidate.variant
    compiled = candidate.compiled
    if variant == "temporal":
        # One temporal launch streams the chunk-deep window and advances
        # TEMPORAL_CHUNK supersteps: the deepened plan's estimate IS that
        # launch's model (same accounting as blocking.plan_blocking), and
        # its useful-GCell/s are directly comparable to a plain superstep's.
        deep = dataclasses.replace(
            candidate.plan,
            par_time=candidate.plan.par_time * TEMPORAL_CHUNK)
        est = estimate(deep, chip, compiled)
    else:
        est = estimate(candidate.plan, chip, compiled)
    decomp = candidate.decomp
    if decomp is not None and decomp.n_devices > 1:
        if grid_shape is None:
            raise ValueError(
                "ranking a decomposed candidate needs grid_shape (exchange "
                "traffic scales with the local extents)")
        plan = candidate.plan
        local = decomp.local_shape(grid_shape)
        blocks = plan.blocks_per_superstep(local)
        # Kernel stream plus, in the interpreter, the executor's
        # padded-carry pass-through (``BlockPlan.run_bytes_per_superstep``
        # on the local extent).
        run_bytes = plan.run_bytes_per_superstep(local, variant, compiled)
        carry_s = (run_bytes - blocks * plan.hbm_bytes_per_block(compiled)) \
            / chip.hbm_bytes_per_s
        t_local = blocks * max(est.compute_s_per_block,
                               est.hbm_s_per_block) + carry_s
        t_ici = exchange_bytes_per_superstep(
            prog, candidate.plan, decomp, grid_shape) \
            / chip.ici_link_bytes_per_s
        t_superstep = max(t_local, t_ici)
        cells_per_s = (decomp.n_devices * math.prod(local)
                       * candidate.plan.par_time) / t_superstep
        useful = grid_useful_fraction(local, candidate.plan.block_shape)
        return RankedCandidate(
            candidate=candidate,
            predicted_gbps=useful * perf_model.gbps_from_cells_per_s(
                cells_per_s, cell_bytes=prog.bytes_per_cell),
            predicted_gcells=useful * cells_per_s / 1e9,
            predicted_gflops=useful * cells_per_s
            * prog.flops_per_cell / 1e9,
            bound="ici" if t_ici > t_local else est.bound,
            hbm_bytes_per_cell=run_bytes
            / (math.prod(local) * plan.par_time),
        )
    if grid_shape is not None:
        # Executor-traffic model: with the grid known, charge exactly what
        # the padded-carry fused run moves per superstep — every block's
        # halo'd read + tile write plus the 2x ping-pong pass-through
        # (``BlockPlan.run_bytes_per_superstep``) — against the compute
        # time of the whole block sweep.  Useful cells are the true grid's
        # (round-up waste shows up as extra blocks, not a fraction), so the
        # grid_useful_fraction penalty is built in rather than multiplied.
        plan = candidate.plan
        blocks = plan.blocks_per_superstep(grid_shape)
        # Temporal: est is the chunk-deep launch's model, so its per-block
        # compute amortizes over the TEMPORAL_CHUNK supersteps the launch
        # advances; run_bytes_per_superstep applies the same amortization
        # to the chunk's marginal HBM traffic.
        t_compute = blocks * est.compute_s_per_block \
            / (TEMPORAL_CHUNK if variant == "temporal" else 1)
        run_bytes = plan.run_bytes_per_superstep(grid_shape, variant,
                                                 compiled)
        t_mem = run_bytes / chip.hbm_bytes_per_s
        t_superstep = max(t_compute, t_mem)
        cells_per_s = math.prod(grid_shape) * plan.par_time / t_superstep
        return RankedCandidate(
            candidate=candidate,
            predicted_gbps=perf_model.gbps_from_cells_per_s(
                cells_per_s, cell_bytes=prog.bytes_per_cell),
            predicted_gcells=cells_per_s / 1e9,
            predicted_gflops=cells_per_s * prog.flops_per_cell / 1e9,
            bound="compute" if t_compute >= t_mem else "memory",
            hbm_bytes_per_cell=run_bytes
            / (math.prod(grid_shape) * plan.par_time),
        )
    # == perf_model.predicted_gbps(prog, plan, chip) on the estimate above
    # (one shared formula, one estimate() evaluation per candidate).
    gbps = perf_model.gbps_from_cells_per_s(est.gcells_per_s,
                                            cell_bytes=prog.bytes_per_cell)
    return RankedCandidate(
        candidate=candidate,
        predicted_gbps=gbps,
        predicted_gcells=est.gcells_per_s / 1e9,
        predicted_gflops=est.gflops_per_s / 1e9,
        bound=est.bound,
        hbm_bytes_per_cell=est.hbm_s_per_block * chip.hbm_bytes_per_s
        / est.plan.useful_cells_per_block(),
    )


def rank(program, candidates: Sequence[Candidate], chip: TpuChip = V5E,
         top_k: Optional[int] = None,
         grid_shape: Optional[Tuple[int, ...]] = None
         ) -> List[RankedCandidate]:
    """Rank candidates by predicted throughput, best first.

    The returned list is non-increasing in ``predicted_gbps`` to 12
    significant digits (``blocking.model_order`` orders the ties);
    ``top_k`` truncates to the measurement frontier.
    """
    ranked = [predict(program, c, chip, grid_shape) for c in candidates]
    ranked.sort(key=lambda r: model_order(r.predicted_gbps,
                                          r.hbm_bytes_per_cell,
                                          r.candidate.halo_aligned,
                                          r.candidate.plan.vmem_bytes),
                reverse=True)
    return ranked if top_k is None else ranked[:top_k]
