"""Stencil serving front: same-shape micro-batching over the unified executor.

The many-independent-grids workload (parameter sweeps, ensembles, per-user
simulations) issues lots of small runs that individually under-utilize the
chip and pay a full dispatch each.  This front queues requests and, on
``flush()``, groups them by (program, grid shape, dtype, steps) and executes
each group through the one front door — ``repro.stencil(program)
.compile(shape, steps=..., batch=B[, devices=N])`` — as batched fused runs:
one donated executable whose pallas grid carries a leading batch dimension,
so B compatible requests cost one dispatch instead of B chains of them.

Requests in a group share the program's canonical coefficients (batching is
only sound when every lane computes the same stencil); incompatible requests
simply land in different groups and still execute, just unbatched.

Blocking plans come from ``compile(plan="model")`` by default (the
zero-state model planner) or ``plan="auto"`` with ``use_autotune=True``
(the autotuner's persistent cache — deterministic, zero search cost after
the first call per shape).

``mesh_devices=N`` compiles batched groups onto an N-device mesh
(``compile(devices=N)``): the mesh-aware autotuner picks the
(plan, decomposition) pair per (program, shape), and the group executes as
a *sharded* batched fused run — one donated multi-device executable (batch
replicated, grid decomposed, one deep-halo exchange per superstep).  Groups
the mesh cannot take (non-divisible shapes, empty sharded space) fall back
to the single-device executor, with the reason recorded in
``mesh_fallbacks``.

CPU-scale usage:
    PYTHONPATH=src python -m repro.launch.stencil_serve \\
        --requests 9 --grid 48,256 --radius 2 --steps 5 --max-batch 4
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis.hw import TpuChip
from repro.core.program import StencilProgram, as_program
from repro.executor import (CompiledStencil, _normalize_variant_request,
                            stencil)
from repro.launch.compile_cache import enable_compile_cache
from repro.tuning.cache import program_fingerprint


@dataclasses.dataclass
class StencilRequest:
    rid: int
    program: StencilProgram
    grid: jnp.ndarray           # (*grid_shape)
    steps: int
    t_submit: float = 0.0       # perf_counter at submit; latency anchor


class ServeStats:
    """Live read-only view over the server's flight recorder.

    The historical counter names survive (``requests``, ``batches``,
    ``batched_requests``, ``sharded_batches``, ``cell_steps``,
    ``seconds``, ``mcell_steps_per_s``) but are now derived from the
    recorder, and ``seconds`` splits into ``compile_seconds`` (dispatch
    time of cold executables — the synchronous trace+compile) and
    ``run_seconds`` (warm dispatches plus the blocking pass).  Queueing
    behaviour is histogrammed: ``latency_percentiles()`` gives
    per-request p50/p95/p99, ``queue_depth``/``batch_occupancy`` samples
    live under the same names on ``recorder``.
    """

    def __init__(self, recorder: "obs.Recorder"):
        self.recorder = recorder

    @property
    def requests(self) -> int:
        return self.recorder.counter("serve.requests")

    @property
    def batches(self) -> int:
        return self.recorder.counter("serve.batches")

    @property
    def batched_requests(self) -> int:
        """Requests that shared their executable with a batch-mate."""
        return self.recorder.counter("serve.batched_requests")

    @property
    def sharded_batches(self) -> int:
        """Batches placed on the device mesh."""
        return self.recorder.counter("serve.sharded_batches")

    @property
    def cell_steps(self) -> int:
        return self.recorder.counter("serve.cell_steps")

    @property
    def compile_seconds(self) -> float:
        return self.recorder.sample_sum("serve.compile_s")

    @property
    def run_seconds(self) -> float:
        return self.recorder.sample_sum("serve.run_s")

    @property
    def seconds(self) -> float:
        return self.compile_seconds + self.run_seconds

    @property
    def mcell_steps_per_s(self) -> float:
        return self.cell_steps / max(self.seconds, 1e-9) / 1e6

    def latency_percentiles(self) -> Dict[str, float]:
        """{"p50": s, "p95": s, "p99": s} of submit->result latency."""
        return self.recorder.percentiles("serve.request_latency_s")


class StencilServer:
    """Queue + group + batched-flush executor for stencil runs.

    ``max_batch`` caps the leading batch axis per executable (VMEM scratch
    is per-block, so the cap is about bounding one dispatch's latency, not
    memory).  ``variant`` selects the kernel lowering for every group
    ("plain" | "pipelined" | "temporal" | "auto"; ``pipelined=True`` is the
    deprecated bool spelling of variant="pipelined").
    """

    def __init__(self, *, max_batch: int = 8,
                 interpret: Optional[bool] = None,
                 pipelined: Optional[bool] = None,
                 variant: Optional[str] = None,
                 use_autotune: bool = False,
                 cache_path: Optional[str] = None,
                 hw: Optional[TpuChip] = None,
                 max_par_time: int = 8,
                 mesh_devices: Optional[int] = None,
                 recorder: Optional["obs.Recorder"] = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1 (got {max_batch})")
        if mesh_devices is not None and mesh_devices < 1:
            raise ValueError(
                f"mesh_devices must be >= 1 (got {mesh_devices})")
        self.max_batch = max_batch
        self.interpret = interpret
        # one normalization rule with the executor: conflicting requests
        # raise RP114, a lone bool warns and maps to its variant name
        self.variant = _normalize_variant_request(variant, pipelined)
        self.pipelined = self.variant == "pipelined"
        self.use_autotune = use_autotune
        self.cache_path = cache_path
        self.hw = hw
        self.max_par_time = max_par_time
        # a 1-device "mesh" is the single-device executor; normalizing here
        # keeps stats.sharded_batches meaning actually-sharded batches
        self.mesh_devices = None if mesh_devices == 1 else mesh_devices
        # explicit recorders record unconditionally (the REPRO_OBS switch
        # gates only the ambient one), so serve stats always work
        self.recorder = recorder if recorder is not None else obs.Recorder()
        self.stats = ServeStats(self.recorder)
        #: (executable identity, steps) pairs that already dispatched once —
        #: their trace+compile cost is paid, later dispatches are warm
        self._warm: set = set()
        self.failed: Dict[int, str] = {}
        #: (program fp, shape) -> why the mesh path declined the group
        self.mesh_fallbacks: Dict[Tuple[str, Tuple[int, ...]], str] = {}
        self._pending: List[StencilRequest] = []
        self._next_rid = 0
        self._programs: Dict[str, StencilProgram] = {}
        #: (fp, shape, batch, on_mesh) -> compiled executable; steps stays
        #: out of the key — run(grid, steps) overrides per call, and
        #: same-remainder step counts share one executable (the mesh
        #: executor's per-(remainder, batch-rank) table lives on the
        #: CompiledStencil's DistributedStencil instance)
        self._compiled: Dict[tuple, CompiledStencil] = {}
        #: (fp, shape, on_mesh) -> (plan, decomp): the plan search runs
        #: once per shape; per-batch compiles pin its result
        self._resolved: Dict[tuple, tuple] = {}

    # -- request intake ------------------------------------------------------

    def submit(self, program, grid, steps: int) -> int:
        """Queue one run; returns the request id resolved by ``flush()``."""
        prog = as_program(program)
        grid = jnp.asarray(grid, dtype=prog.dtype)
        if grid.ndim != prog.ndim:
            raise ValueError(
                f"request grid rank {grid.ndim} != program ndim {prog.ndim}")
        if steps < 0:
            raise ValueError("steps must be >= 0")
        rid = self._next_rid
        self._next_rid += 1
        self._pending.append(
            StencilRequest(rid, prog, grid, steps,
                           t_submit=time.perf_counter()))
        return rid

    def pending(self) -> int:
        return len(self._pending)

    # -- compilation ---------------------------------------------------------

    def _compiled_for(self, program: StencilProgram, shape: Tuple[int, ...],
                      steps: int, batch: Optional[int],
                      on_mesh: bool) -> CompiledStencil:
        """Front-door executable for one chunk shape, memoized per server.

        ``steps`` only seeds the first compile of a key — every flush
        passes its own count to ``run`` — so the executable (and the mesh
        executor's per-remainder table behind it) is shared across step
        counts.  The plan policy mirrors the historical server: the
        autotuner's persistent cache when the caller opted in
        (``use_autotune`` / explicit ``cache_path``), the pure model
        planner otherwise — and on the mesh always the mesh-aware tuner
        (model-only), touching the persistent cache only under the same
        opt-in.
        """
        fp = program_fingerprint(program)
        key = (fp, shape, batch, on_mesh)
        cs = self._compiled.get(key)
        if cs is None:
            opted_in = self.use_autotune or self.cache_path is not None
            resolved = self._resolved.get((fp, shape, on_mesh))
            if resolved is None:
                plan = "auto" if (on_mesh or self.use_autotune) else "model"
                devices = self.mesh_devices if on_mesh else None
            else:       # later step counts / chunk sizes pin the search's
                plan, devices = resolved        # (plan, decomposition)
            cs = stencil(program).compile(
                shape, steps=steps, batch=batch, devices=devices,
                plan=plan, variant=self.variant,
                interpret=self.interpret, hw=self.hw,
                max_par_time=self.max_par_time,
                cache=opted_in, cache_path=self.cache_path)
            self._resolved[(fp, shape, on_mesh)] = (cs.plan, cs.decomp)
            self._compiled[key] = cs
        return cs

    def _mesh_ok(self, program: StencilProgram,
                 shape: Tuple[int, ...]) -> bool:
        return self.mesh_devices is not None and \
            (program_fingerprint(program), shape) not in self.mesh_fallbacks

    # -- execution -----------------------------------------------------------

    def _group_key(self, req: StencilRequest):
        fp = program_fingerprint(req.program)
        self._programs.setdefault(fp, req.program)
        return (fp, tuple(req.grid.shape), str(req.grid.dtype), req.steps)

    def flush(self) -> Dict[int, np.ndarray]:
        """Run every pending request; returns ``{rid: result grid}``.

        Groups are formed by (program, shape, dtype, steps) and executed in
        ``max_batch``-sized batched fused runs; a group of one still goes
        through the same executor, just without the batch axis.  Group
        failures are isolated: a group whose plan or execution raises loses
        only its own requests — their rids land in ``self.failed`` with the
        error — and every other group's results are still returned.  A
        group the mesh refuses falls back to the single-device executor
        (reason in ``mesh_fallbacks``) before counting as failed.
        """
        rec = self.recorder
        pending, self._pending = self._pending, []
        rec.observe("serve.queue_depth", float(len(pending)))
        groups: Dict[tuple, List[StencilRequest]] = {}
        for req in pending:
            groups.setdefault(self._group_key(req), []).append(req)

        results: Dict[int, np.ndarray] = {}
        failed_before = len(self.failed)
        outs = []
        with rec.span("serve.flush", requests=len(pending),
                      groups=len(groups)) as flush_span:
            for (fp, shape, _dtype, steps), reqs in groups.items():
                program = self._programs[fp]
                done = 0     # requests of this group whose chunk already ran
                if steps == 0:      # identity: results are the inputs, no run
                    for lo in range(0, len(reqs), self.max_batch):
                        chunk = reqs[lo:lo + self.max_batch]
                        outs.append((chunk,
                                     jnp.stack([r.grid for r in chunk])))
                        self._count_chunk(chunk, shape, steps)
                    continue
                try:
                    on_mesh = self._mesh_ok(program, shape)
                    if on_mesh:
                        try:
                            # resolve plan + decomposition once per group; a
                            # refusal (non-divisible shape, empty sharded
                            # space) demotes the group, not the flush
                            t0 = time.perf_counter()
                            self._compiled_for(program, shape, steps,
                                               len(reqs[:self.max_batch]),
                                               on_mesh=True)
                            rec.observe("serve.compile_s",
                                        time.perf_counter() - t0)
                        except Exception as e:
                            self.mesh_fallbacks[(fp, shape)] = \
                                f"{type(e).__name__}: {e}"
                            on_mesh = False
                    for lo in range(0, len(reqs), self.max_batch):
                        chunk = reqs[lo:lo + self.max_batch]
                        t0 = time.perf_counter()
                        if on_mesh:
                            # mesh path: batched sharded fused run — one
                            # donated multi-device executable per chunk
                            cs = self._compiled_for(program, shape, steps,
                                                    len(chunk), on_mesh=True)
                            out = cs.run(jnp.stack([r.grid for r in chunk]),
                                         steps)
                            outs.append((chunk, out))
                            rec.count("serve.sharded_batches")
                        elif len(chunk) == 1:
                            cs = self._compiled_for(program, shape, steps,
                                                    None, on_mesh=False)
                            out = cs.run(chunk[0].grid, steps)
                            outs.append((chunk, out[jnp.newaxis]))
                        else:
                            cs = self._compiled_for(program, shape, steps,
                                                    len(chunk), on_mesh=False)
                            out = cs.run(jnp.stack([r.grid for r in chunk]),
                                         steps)
                            outs.append((chunk, out))
                        # first dispatch of an (executable, steps) pair is
                        # the synchronous trace+compile; later ones enqueue
                        wkey = (id(cs), steps)
                        cold = wkey not in self._warm
                        self._warm.add(wkey)
                        rec.observe(
                            "serve.compile_s" if cold else "serve.run_s",
                            time.perf_counter() - t0)
                        done += len(chunk)
                        self._count_chunk(chunk, shape, steps)
                except Exception as e:  # plan/compile failure: fail the rest
                    for req in reqs[done:]:
                        self.failed[req.rid] = f"{type(e).__name__}: {e}"
            # Resolution is a separate pass so dispatches overlap across
            # groups; execution errors surface asynchronously at
            # block_until_ready, so isolation must hold here too — a chunk
            # whose executable fails at runtime fails only its own rids.
            t0 = time.perf_counter()
            for chunk, out in outs:
                try:
                    out = np.asarray(jax.block_until_ready(out))
                except Exception as e:
                    for req in chunk:
                        self.failed[req.rid] = f"{type(e).__name__}: {e}"
                    continue
                t_done = time.perf_counter()
                for i, req in enumerate(chunk):
                    results[req.rid] = out[i]
                    rec.observe("serve.request_latency_s",
                                t_done - req.t_submit)
            rec.observe("serve.run_s", time.perf_counter() - t0)
            rec.count("serve.requests", len(pending))
            newly_failed = len(self.failed) - failed_before
            if newly_failed:
                rec.count("serve.failed", newly_failed)
            flush_span.set(results=len(results), failed=newly_failed)
        return results

    def _count_chunk(self, chunk: List[StencilRequest],
                     shape: Tuple[int, ...], steps: int) -> None:
        rec = self.recorder
        rec.count("serve.batches")
        rec.observe("serve.batch_occupancy", len(chunk) / self.max_batch)
        if len(chunk) > 1:
            rec.count("serve.batched_requests", len(chunk))
        if steps:
            rec.count("serve.cell_steps",
                      len(chunk) * int(np.prod(shape)) * steps)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=9)
    ap.add_argument("--grid", default="48,256",
                    help="grid shape per request, e.g. 48,256 or 8,16,128")
    ap.add_argument("--ndim", type=int, default=None, choices=(2, 3),
                    help="defaults to len(--grid)")
    ap.add_argument("--radius", type=int, default=2)
    ap.add_argument("--shape", default="star",
                    choices=("star", "box", "diamond"))
    ap.add_argument("--boundary", default="clamp",
                    choices=("clamp", "periodic", "constant"))
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--variant", default=None,
                    choices=("auto", "plain", "pipelined", "temporal"),
                    help="kernel lowering for every group")
    ap.add_argument("--pipelined", action="store_true",
                    help="deprecated alias for --variant pipelined")
    ap.add_argument("--autotune", action="store_true",
                    help="plans from the autotuner cache (model-guided)")
    ap.add_argument("--mesh-devices", type=int, default=None,
                    help="place batched groups onto an N-device mesh "
                         "(needs N visible devices, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    shape = tuple(int(p) for p in args.grid.split(",") if p)
    ndim = args.ndim or len(shape)
    program = StencilProgram(ndim=ndim, radius=args.radius,
                             shape=args.shape, boundary=args.boundary)
    server = StencilServer(max_batch=args.max_batch,
                           variant="pipelined" if args.pipelined
                           else args.variant,
                           use_autotune=args.autotune,
                           mesh_devices=args.mesh_devices)
    rng = np.random.RandomState(0)
    rids = [server.submit(program, rng.uniform(-1, 1, shape), args.steps)
            for _ in range(args.requests)]
    results = server.flush()
    s = server.stats
    lat = s.latency_percentiles()
    print(f"[stencil-serve] {s.requests} requests -> {s.batches} batches "
          f"({s.batched_requests} batched, {s.sharded_batches} sharded), "
          f"{s.compile_seconds * 1e3:.1f} ms compile + "
          f"{s.run_seconds * 1e3:.1f} ms run, "
          f"{s.mcell_steps_per_s:.1f} Mcell-steps/s")
    print(f"[stencil-serve] request latency "
          f"p50={lat['p50'] * 1e3:.1f} ms p95={lat['p95'] * 1e3:.1f} ms "
          f"p99={lat['p99'] * 1e3:.1f} ms")
    for key, why in server.mesh_fallbacks.items():
        print(f"[stencil-serve] mesh fallback {key[1]}: {why}")
    for rid in rids[:2]:
        if rid in results:
            g = results[rid]
            print(f"[stencil-serve] rid={rid} out_shape={g.shape} "
                  f"mean={float(g.mean()):+.5f}")
    for rid, why in sorted(server.failed.items()):
        print(f"[stencil-serve] rid={rid} FAILED: {why}")
    return 1 if server.failed else 0


if __name__ == "__main__":
    sys.exit(main())
