"""Empirical measurement harness — the paper's "Measured Performance" column.

For each model-ranked candidate: lower through the backend registry, run a
warmup (compile/trace outside the timed region), then time repeated
*steady-state fused runs* — ``supersteps`` chained supersteps through the
donated run executor (``ops.stencil_run``'s one-executable path) — with
``block_until_ready``.  Timing multi-superstep runs matters: a lone
superstep dispatch charges the whole Python/jit dispatch overhead to one
superstep, which on small grids dwarfs the kernel and made
``us_per_superstep`` useless for ranking; the fused run amortizes it to
O(1/supersteps).  Reported metrics mirror paper Table III for *our*
hardware:

  achieved GB/s      — useful cells/s x Table I bytes/cell (effective BW)
  achieved GFLOP/s   — useful cells/s x tap-set FLOP/cell
  model accuracy     — measured / model-estimated effective GB/s (the
                       paper's Table III "Model Accuracy" column)

Each measured candidate files that ratio as an accuracy sample through
``repro.obs.record_accuracy`` (a no-op unless the flight recorder is on),
keyed by the tuning cache key, for the history ledger.

A candidate that fails to lower, compile, or execute (Pallas rejects some
shape/padding combinations; a backend may be unavailable off-TPU) yields a
``Measurement`` with ``ok=False`` carrying the error — the tuner skips it
and moves down the frontier instead of crashing the search.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import List, Optional, Sequence, Tuple

import jax

from repro import obs
from repro.analysis.hw import TpuChip, V5E
from repro.core import reference as ref
from repro.core.program import as_program
from repro.backends import lower
from repro.tuning.cache import cache_key
from repro.tuning.model_rank import RankedCandidate, predict
from repro.tuning.space import Candidate


@dataclasses.dataclass(frozen=True)
class Measurement:
    """Empirical result for one candidate (``ok=False`` => failed to run)."""

    ranked: RankedCandidate
    ok: bool
    error: Optional[str] = None
    error_class: Optional[str] = None  # exception type name of the skip
    stage: Optional[str] = None        # where it died: lower/warmup/timed
    us_per_superstep: float = 0.0
    achieved_gcells: float = 0.0   # useful GCell/s
    achieved_gbps: float = 0.0     # effective GB/s (Table I bytes/cell)
    achieved_gflops: float = 0.0   # useful GFLOP/s
    model_accuracy: float = 0.0    # measured/estimated (paper Table III col.)

    @property
    def candidate(self) -> Candidate:
        return self.ranked.candidate

    def describe(self) -> str:
        if not self.ok:
            where = f" at {self.stage}" if self.stage else ""
            return f"{self.candidate.describe()} -> FAILED{where}: {self.error}"
        return (f"{self.candidate.describe()} -> "
                f"{self.achieved_gbps:.3f} GB/s measured vs "
                f"{self.ranked.predicted_gbps:.3f} est "
                f"(accuracy {self.model_accuracy:.2f}, "
                f"{self.us_per_superstep:.0f} us/superstep)")


def _failed(ranked: RankedCandidate, err: BaseException,
            stage: str) -> Measurement:
    cls = type(err).__name__
    obs.count("tuning.measure_skip")
    obs.count(f"tuning.measure_skip.{cls}")
    obs.event("measure_skip", candidate=ranked.candidate.describe(),
              backend=f"{ranked.candidate.backend}"
                      f"@{ranked.candidate.backend_version}",
              stage=stage, error_class=cls, error=str(err))
    return Measurement(ranked=ranked, ok=False,
                       error=f"{cls}: {err}", error_class=cls, stage=stage)


def measure_candidate(
    program,
    ranked: RankedCandidate,
    grid_shape: Tuple[int, ...],
    *,
    warmup: int = 1,
    reps: int = 2,
    supersteps: int = 2,
    seed: int = 0,
    chip: TpuChip = V5E,
) -> Measurement:
    """Time ``supersteps`` fused supersteps of one candidate on a
    ``grid_shape`` grid; ``us_per_superstep`` is the steady-state
    per-superstep cost (dispatch overhead amortized over the fused run).
    ``chip`` is the chip the candidate was ranked for (the accuracy
    sample's key).

    ``warmup``/``reps``/``supersteps`` are honored exactly as given:
    ``warmup=0`` really skips warmup (the compile lands in the timed region
    — the honest number when a caller asks for cold-start cost), and
    ``reps``/``supersteps`` below 1 are caller errors, not candidate
    failures, so they raise instead of yielding ``ok=False``.

    Never raises for a *broken candidate*: lowering, compilation, and
    execution errors are captured in the returned ``Measurement``.
    """
    if reps < 1:
        raise ValueError(f"reps must be >= 1 (got {reps})")
    if warmup < 0:
        raise ValueError(f"warmup must be >= 0 (got {warmup})")
    if supersteps < 1:
        raise ValueError(f"supersteps must be >= 1 (got {supersteps})")
    prog = as_program(program)
    cand = ranked.candidate
    steps = cand.plan.par_time * supersteps
    stage = "lower"
    try:
        lowered = lower(prog, cand.plan, backend=cand.backend,
                        version=cand.backend_version)
        grid = ref.random_grid(prog, grid_shape, seed=seed)
        fn = jax.jit(lambda g: lowered.run(g, steps))
        stage = "warmup"    # first call = trace + compile
        for _ in range(warmup):
            jax.block_until_ready(fn(grid))
        stage = "timed"
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(grid)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / (reps * supersteps)
    except Exception as e:  # lowering/compile/runtime failure: skip, not crash
        return _failed(ranked, e, stage)

    useful_cells = math.prod(grid_shape) * cand.plan.par_time
    gcells = useful_cells / dt / 1e9
    gbps = gcells * prog.bytes_per_cell
    accuracy = gbps / ranked.predicted_gbps if ranked.predicted_gbps else 0.0
    obs.record_accuracy(
        key=cache_key(prog, grid_shape, chip.name, cand.backend,
                      cand.backend_version),
        chip=chip.name, backend=cand.backend,
        backend_version=cand.backend_version, variant=cand.variant,
        grid_shape=list(grid_shape), batch=None, steps=steps,
        block_shape=list(cand.plan.block_shape),
        par_time=cand.plan.par_time, decomp=None,
        predicted_gbps=ranked.predicted_gbps, achieved_gbps=gbps,
        model_accuracy=accuracy, mcells_per_s=gcells * 1e3,
        source="tuning.measure")
    return Measurement(
        ranked=ranked,
        ok=True,
        us_per_superstep=dt * 1e6,
        achieved_gcells=gcells,
        achieved_gbps=gbps,
        achieved_gflops=gcells * prog.flops_per_cell,
        model_accuracy=accuracy,
    )


def measure_frontier(
    program,
    frontier: Sequence[RankedCandidate],
    grid_shape: Tuple[int, ...],
    *,
    warmup: int = 1,
    reps: int = 2,
    supersteps: int = 2,
    seed: int = 0,
    chip: TpuChip = V5E,
) -> List[Measurement]:
    """Measure every frontier candidate; failures are kept (``ok=False``)
    so the caller can report *why* a model favourite did not survive."""
    return [measure_candidate(program, r, grid_shape,
                              warmup=warmup, reps=reps,
                              supersteps=supersteps, seed=seed, chip=chip)
            for r in frontier]


def measure_candidates(
    program,
    candidates: Sequence[Candidate],
    grid_shape: Tuple[int, ...],
    chip: TpuChip = V5E,
    **kwargs,
) -> List[Measurement]:
    """Convenience: predict + measure raw candidates (used by tests/CLI to
    sweep a whole small space rather than a ranked frontier)."""
    frontier = [predict(program, c, chip, grid_shape) for c in candidates]
    return measure_frontier(program, frontier, grid_shape, chip=chip,
                            **kwargs)


def best_measurement(
        measurements: Sequence[Measurement]) -> Optional[Measurement]:
    """Highest achieved throughput among the candidates that ran."""
    ok = [m for m in measurements if m.ok]
    return max(ok, key=lambda m: m.achieved_gcells) if ok else None
