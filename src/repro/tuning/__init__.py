"""Plan autotuning: model-guided design-space exploration with an empirical
measurement harness and a persistent plan cache.

The paper's §V.A methodology, made a subsystem (the direction SASA
(arXiv 2208.10770) and Stencil-HMLS (arXiv 2310.01914) push):

    enumerate (space.py)  — every legal (bsize, par_time, backend) point,
                            pruned by eq. 2 / VMEM budget / alignment
    rank      (model_rank)— perf-model roofline ranking; keep the top-K
                            frontier worth paying for measurements
    measure   (measure.py)— lower + time each frontier candidate; record
                            GB/s, GFLOP/s, and the model-accuracy ratio
    cache     (cache.py)  — persist the winner keyed by (program, grid,
                            chip, backend@version); serving pays zero
                            search cost

One call does all four::

    from repro.tuning import autotune
    tuned = autotune(program, chip, grid_shape=(16384, 16384))
    lowered = lower(program, tuned.plan, backend=tuned.backend)
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

from repro.analysis.hw import TpuChip, V5E
from repro.backends.registry import (default_backend_name, get_backend,
                                     variant_of)
from repro.core.blocking import VARIANTS, BlockPlan
from repro.core.program import StencilProgram, as_program
from repro.tuning import model_rank as _model_rank
from repro.tuning import space as _space
from repro.tuning.cache import PlanCache, cache_key, program_fingerprint
from repro.tuning.measure import (Measurement, best_measurement,
                                  measure_candidates, measure_frontier)
from repro.tuning.model_rank import RankedCandidate, predict, rank
from repro.tuning.space import (Candidate, MeshDecomposition, default_bsizes,
                                enumerate_decompositions, enumerate_space)

__all__ = [
    "Candidate",
    "Measurement",
    "MeshDecomposition",
    "PlanCache",
    "RankedCandidate",
    "TunedPlan",
    "autotune",
    "best_measurement",
    "cache_key",
    "default_bsizes",
    "enumerate_decompositions",
    "enumerate_space",
    "measure_candidates",
    "measure_frontier",
    "predict",
    "program_fingerprint",
    "rank",
]


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """The autotuner's answer: a plan, where it came from, and what it did.

    ``measurement`` is None when tuning ran model-only (``measure=False``)
    or when every frontier candidate failed to run (the model's top pick is
    still returned — the paper equally falls back to the model when a
    bitstream will not route).
    """

    program: StencilProgram
    plan: BlockPlan
    backend: str
    backend_version: int
    predicted_gbps: float
    measurement: Optional[Measurement]
    from_cache: bool
    key: str
    space_size: int = 0
    frontier_size: int = 0
    # winning mesh decomposition (shards per grid axis); None = single device
    decomp: Optional[Tuple[int, ...]] = None
    # kernel lowering of the winning backend ("plain"|"pipelined"|"temporal")
    variant: str = "plain"
    # bounds the winning plan was searched under (cache-coverage checks)
    searched_max_par_time: int = 0
    searched_bsizes: Optional[Tuple[Tuple[int, ...], ...]] = None

    @property
    def measured_gbps(self) -> float:
        return self.measurement.achieved_gbps if self.measurement else 0.0

    def to_record(self) -> dict:
        """JSON-serializable cache record."""
        m = self.measurement
        return {
            "program": dataclasses.asdict(self.program),
            "block_shape": list(self.plan.block_shape),
            "par_time": self.plan.par_time,
            "backend": self.backend,
            "backend_version": self.backend_version,
            "predicted_gbps": self.predicted_gbps,
            "space_size": self.space_size,
            "frontier_size": self.frontier_size,
            "decomp": None if self.decomp is None else list(self.decomp),
            "variant": self.variant,
            "search": {
                "max_par_time": self.searched_max_par_time,
                "bsizes": None if self.searched_bsizes is None
                else [list(b) for b in self.searched_bsizes],
            },
            "measurement": None if m is None else {
                "us_per_superstep": m.us_per_superstep,
                "achieved_gcells": m.achieved_gcells,
                "achieved_gbps": m.achieved_gbps,
                "achieved_gflops": m.achieved_gflops,
                "model_accuracy": m.model_accuracy,
            },
        }


def _from_record(program: StencilProgram, record: dict,
                 key: str) -> TunedPlan:
    plan = BlockPlan(spec=program,
                     block_shape=tuple(record["block_shape"]),
                     par_time=int(record["par_time"]))
    m = record.get("measurement")
    measurement = None
    if m is not None:
        ranked = _model_rank.RankedCandidate(
            candidate=Candidate(plan=plan, backend=record["backend"],
                                backend_version=record["backend_version"],
                                halo_aligned=_space.halo_aligned(
                                    plan.par_time, program.halo_radius),
                                variant=record.get("variant", "plain")),
            predicted_gbps=record["predicted_gbps"],
            predicted_gcells=0.0, predicted_gflops=0.0, bound="cached")
        measurement = Measurement(ranked=ranked, ok=True, **m)
    search = record.get("search") or {}
    return TunedPlan(program=program, plan=plan,
                     backend=record["backend"],
                     backend_version=record["backend_version"],
                     predicted_gbps=record["predicted_gbps"],
                     measurement=measurement, from_cache=True, key=key,
                     space_size=record.get("space_size", 0),
                     frontier_size=record.get("frontier_size", 0),
                     decomp=None if record.get("decomp") is None
                     else tuple(record["decomp"]),
                     variant=record.get("variant", "plain"),
                     searched_max_par_time=int(
                         search.get("max_par_time", 0)),
                     searched_bsizes=None if search.get("bsizes") is None
                     else tuple(tuple(b) for b in search["bsizes"]))


def _record_satisfies(record: dict, program: StencilProgram,
                      grid_shape: Tuple[int, ...], *,
                      measure: bool,
                      bsizes: Optional[Sequence[Tuple[int, ...]]],
                      max_par_time: int, top_k: int) -> bool:
    """A cached record only counts as a hit when it can honor the current
    request, in both directions:

    * the requested search space must be *covered* by the space the record
      was searched under (a winner found with ``max_par_time=4`` says
      nothing about a ``max_par_time=32`` request);
    * the cached winner must itself lie inside the requested space (the
      argmax over a superset that lands in the subset is the subset's
      argmax too; one that lands outside says nothing), and
    * asking for empirical tuning is never satisfied by a model-only
      record; a *partially* measured record (frontier < space) transfers
      only to requests with the exact same bounds and a frontier no wider
      — a differently-bounded request would rank a different frontier with
      unmeasured members.  A fully measured space transfers freely (its
      winner is the empirical argmax, subject to the membership check).
    """
    search = record.get("search") or {}
    cached_bs = search.get("bsizes")

    if measure:
        if record.get("measurement") is None:
            return False
        frontier = int(record.get("frontier_size", 0))
        if frontier < int(record.get("space_size", 0)):
            same_bounds = (
                max_par_time == int(search.get("max_par_time", 0))
                and (sorted(tuple(b) for b in bsizes)
                     if bsizes is not None else None)
                == (sorted(tuple(b) for b in cached_bs)
                    if cached_bs is not None else None))
            if not (same_bounds and top_k <= frontier):
                return False

    # requested space ⊆ searched space
    if max_par_time > int(search.get("max_par_time", 0)):
        return False
    if bsizes is None:
        if cached_bs is not None:
            return False            # cached search was restricted; ours isn't
    else:
        cover = default_bsizes(program.ndim, grid_shape) \
            if cached_bs is None else cached_bs
        if not {tuple(b) for b in bsizes} <= {tuple(b) for b in cover}:
            return False

    # cached winner ∈ requested space
    pt = int(record["par_time"])
    if pt > max_par_time:
        return False
    if bsizes is not None:
        halo = pt * program.halo_radius
        bsize = tuple(b + 2 * halo for b in record["block_shape"])
        if bsize not in {tuple(b) for b in bsizes}:
            return False
    return True


def autotune(
    program,
    chip: TpuChip = V5E,
    *,
    grid_shape: Tuple[int, ...],
    backend: Optional[str] = None,
    backend_version: Optional[int] = None,
    variant: Optional[str] = None,
    top_k: int = 5,
    measure: bool = True,
    cache: bool = True,
    cache_path: Optional[str] = None,
    force: bool = False,
    bsizes: Optional[Sequence[Tuple[int, ...]]] = None,
    max_par_time: int = 32,
    n_devices: Optional[int] = None,
    decomposition: Optional[Tuple[int, ...]] = None,
    warmup: int = 1,
    reps: int = 2,
    supersteps: int = 2,
    seed: int = 0,
) -> TunedPlan:
    """Tune ``program`` for ``chip`` on a ``grid_shape`` workload.

    Search -> rank -> measure -> cache.  A cache hit short-circuits the
    whole pipeline (no enumeration, no measurement) — but only when the
    cached record can honor this call (``measure=True`` is never satisfied
    by a model-only record, and a plan from outside an explicit
    ``bsizes``/``max_par_time`` restriction re-tunes); ``force=True``
    re-tunes and overwrites unconditionally.  ``measure=False`` trusts the model's top
    pick (the cheap, deterministic mode configs/CI use); ``measure=True``
    times the top-``top_k`` frontier and lets the empirical winner
    override the model (the paper's own Table III showed the model 13-45%
    off measured — measuring the frontier is how mispredictions get
    corrected).

    ``variant`` controls the kernel-variant search axis: ``None`` pins the
    backend name exactly as given (the legacy behavior — an explicitly
    ``-pipelined`` name stays pipelined); ``"auto"`` searches every
    registered variant sibling of ``backend`` (plain / pipelined /
    temporal where lowerings exist) and lets the ranking pick; a concrete
    variant name resolves the sibling and pins it.  The request is part of
    the cache key — a winner found under one variant policy never serves
    another.

    ``n_devices`` puts the mesh decomposition on the search axis (every
    feasible split of that many devices over the grid, per-shard halo
    pruning applied); ``decomposition`` pins an explicit shards-per-axis
    split instead.  Mesh-aware tuning is model-only — the measurement
    harness runs on the local chip, and timing a sharded run takes a real
    mesh (``core.distributed``) — so pass ``measure=False``; the winning
    split lands in ``TunedPlan.decomp`` and its own cache key (a plan
    tuned for one mesh never serves another).
    """
    prog = as_program(program)
    name = backend or default_backend_name()
    if variant is None or variant == "auto":
        search_backends = (name,)
        if variant == "auto":
            search_backends = tuple(
                n for n in (variant_of(name, v) for v in VARIANTS)
                if n is not None)
    else:
        sibling = variant_of(name, variant)
        if sibling is None:
            raise ValueError(
                f"backend {name!r} has no {variant!r} lowering to tune; "
                f"pick a pallas backend or variant='auto'")
        name = sibling
        search_backends = (name,)
    _, version = get_backend(name, backend_version)

    decomp_req = None
    if decomposition is not None:
        decomp_req = tuple(int(s) for s in decomposition)
    elif n_devices is not None:
        decomp_req = f"ndev={n_devices}"
    if decomp_req is not None and measure:
        raise ValueError(
            "mesh-aware tuning is model-only (the harness cannot time a "
            "sharded run on the local chip); pass measure=False")

    key = cache_key(prog, grid_shape, chip.name, name, version,
                    decomp=decomp_req, variant=variant)
    store = PlanCache(cache_path) if cache else None

    if store is not None and not force:
        for record in store.get_all(key):
            if _record_satisfies(record, prog, grid_shape, measure=measure,
                                 bsizes=bsizes, max_par_time=max_par_time,
                                 top_k=top_k):
                return _from_record(prog, record, key)

    decomps = None
    if decomposition is not None:
        decomps = (MeshDecomposition(tuple(int(s) for s in decomposition)),)
    candidates = enumerate_space(
        prog, chip, backends=search_backends, backend_version=backend_version,
        bsizes=bsizes, grid_shape=grid_shape, max_par_time=max_par_time,
        n_devices=None if decomps is not None else n_devices,
        decompositions=decomps)
    if not candidates:
        raise ValueError(
            f"empty design space for {prog} on {chip.name} "
            f"(grid {grid_shape}) — relax bsizes/max_par_time"
            + ("/decomposition" if decomp_req is not None else ""))

    ranked = rank(prog, candidates, chip, grid_shape=grid_shape)
    frontier = ranked[:max(top_k, 1)]

    winner: RankedCandidate = frontier[0]
    measurement: Optional[Measurement] = None
    if measure:
        results = measure_frontier(prog, frontier, grid_shape,
                                   warmup=warmup, reps=reps,
                                   supersteps=supersteps, seed=seed,
                                   chip=chip)
        measurement = best_measurement(results)
        if measurement is not None:
            winner = measurement.ranked

    tuned = TunedPlan(
        program=prog,
        plan=winner.candidate.plan,
        backend=winner.candidate.backend,
        backend_version=winner.candidate.backend_version,
        predicted_gbps=winner.predicted_gbps,
        measurement=measurement,
        from_cache=False,
        key=key,
        space_size=len(candidates),
        frontier_size=len(frontier),
        decomp=None if winner.candidate.decomp is None
        else winner.candidate.decomp.axis_shards,
        variant=winner.candidate.variant,
        searched_max_par_time=max_par_time,
        searched_bsizes=None if bsizes is None
        else tuple(tuple(b) for b in bsizes),
    )
    if store is not None:
        store.add(key, tuned.to_record())
    return tuned
