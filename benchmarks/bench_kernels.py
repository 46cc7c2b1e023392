"""Wall-clock microbenchmarks of the stencil kernels (CPU, interpret mode).

These numbers are CPU-interpreter timings — they validate the measurement
harness and relative blocking behaviour, NOT TPU performance (that is the
roofline analysis' job).  Derived column reports MCell/s and the speedup of
temporal blocking vs par_time=1 at equal steps.

Every row runs through the unified executor —
``repro.stencil(program).compile(shape, steps=..., plan=..., backend=...)``
— so the benchmark exercises exactly the production entry point.
Executor-comparison rows time the fused run executor vs the eager
per-superstep chain, the double-buffered (pipelined) kernel vs the plain
one, and a batched ``(B, *grid)`` executable vs a per-grid Python loop.

Env knobs:
  REPRO_BENCH_TUNED=1      — blocked plans from the autotuner's persistent
                             cache (``repro.tuning``, model-guided mode)
                             instead of the hand-written block shapes.
  REPRO_BENCH_SMOKE=1      — one small 2D case only (CI's per-PR artifact).
  REPRO_BENCH_BACKEND=NAME — pin the registry backend (e.g. xla-reference
                             for pallas-free CI runners); the pallas-only
                             comparison rows are skipped for non-default
                             backends.
"""

import os
import time

import jax
import jax.numpy as jnp

import repro
from repro.backends import variant_of
from repro.core import reference as ref
from repro.core.blocking import TEMPORAL_CHUNK, BlockPlan
from repro.core.perf_model import gbps_from_cells_per_s
from repro.core.program import StencilProgram
from repro.kernels import ops


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _bytes_accessed(fn, *args):
    """XLA ``cost_analysis()`` "bytes accessed" of the jitted ``fn`` on
    ``args`` — the compiler's static count of HBM bytes the executable
    touches (the quantity the padded-carry executor halved).  Returns None
    when the backend/compiler does not expose the counter."""
    try:
        cost = jax.jit(fn).lower(*args).compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        ba = cost.get("bytes accessed")
        return int(ba) if ba is not None else None
    except Exception:
        return None


def _with_bytes(derived: str, fn, *args) -> str:
    ba = _bytes_accessed(fn, *args)
    return derived if ba is None else f"{derived};bytes_accessed={ba}"


def _acc_fields(cs, cells_per_s: float) -> str:
    """Per-row model-accuracy telemetry: resolved backend, achieved
    effective GB/s, and the paper's Table III ratio (measured/estimated)
    against the plan's perf-model estimate."""
    gbps = gbps_from_cells_per_s(cells_per_s, cs.program.bytes_per_cell)
    pred = cs.cost.predicted_gbps
    acc = gbps / pred if pred else 0.0
    return (f"backend={cs.backend};achieved_gbps={gbps:.4f};"
            f"model_accuracy={acc:.4f}")


def _verify_ms(prog, plan, shape, reps=10) -> float:
    """Best-of-``reps`` wall time of the static pre-flight (repro.lint's
    verifier) for one compile configuration, in milliseconds.  Reported
    per row so the artifact proves the fail-fast check stays sub-1ms —
    pure integer arithmetic, no tracing (guarded in tests/test_lint.py)."""
    from repro.lint import verify

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        verify(prog, plan, shape)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _tuned_plan(prog, grid_shape) -> BlockPlan:
    """Cached model-guided plan for this bench grid (zero search cost after
    the first call thanks to the plan cache)."""
    from repro.tuning import autotune

    tuned = autotune(prog, grid_shape=grid_shape, measure=False,
                     max_par_time=4)
    return tuned.plan


def _executor_rows(prog, shape, plan, rows):
    """Fused-vs-eager, pipelined-vs-plain, and batched-vs-loop comparisons
    on one program (the front door's direct pallas dispatch path)."""
    sten = repro.stencil(prog)
    g = ref.random_grid(prog, shape, seed=0)
    cells = 1
    for s in shape:
        cells *= s
    steps = 2 * plan.par_time
    cs = sten.compile(shape, steps=steps, plan=plan)

    def eager():
        # the historical per-superstep Python chain (one dispatch per
        # superstep, remainder folded) — the executor's own un-fused
        # control path, so fused and eager stay one implementation
        return ops._stencil_run(g, prog, sten.coeffs, plan, steps,
                                fused=False)

    t_eager = _time(eager, reps=2)
    t_fused = _time(cs.run, g, reps=2)
    mcells = cells * steps / t_fused / 1e6
    rows.append((f"run_fused_{prog.ndim}d_r{prog.radius}", t_fused * 1e6,
                 _with_bytes(
                     f"mcells_per_s={mcells:.1f};"
                     f"fused_speedup_vs_eager={t_eager / t_fused:.2f}x;"
                     f"{_acc_fields(cs, cells * steps / t_fused)}",
                     cs.run, g)))

    cs_pipe = sten.compile(shape, steps=steps, plan=plan,
                           variant="pipelined")
    t_pipe = _time(cs_pipe.run, g, reps=2)
    rows.append((f"run_pipelined_{prog.ndim}d_r{prog.radius}", t_pipe * 1e6,
                 _with_bytes(
                     f"mcells_per_s={cells * steps / t_pipe / 1e6:.1f};"
                     f"pipelined_speedup_vs_plain={t_fused / t_pipe:.2f}x;"
                     f"{_acc_fields(cs_pipe, cells * steps / t_pipe)}",
                     cs_pipe.run, g)))

    if variant_of(cs.backend, "temporal"):
        # Temporally-fused rows: one launch per TEMPORAL_CHUNK-superstep
        # chunk.  The marginal *modeled* HBM bytes per superstep must
        # undercut plain whenever par_time >= 2 (the ~1/C traffic claim);
        # the interpreter's cost_analysis charges compute passes, not DMA,
        # so the regression guard rides the analytic model.
        steps_t = TEMPORAL_CHUNK * plan.par_time
        cs_pt = sten.compile(shape, steps=steps_t, plan=plan)
        cs_t = sten.compile(shape, steps=steps_t, plan=plan,
                            variant="temporal")
        t_plain_t = _time(cs_pt.run, g, reps=2)
        t_temporal = _time(cs_t.run, g, reps=2)
        compiled = not cs_t.interpret
        mb_plain = plan.run_bytes_per_superstep(shape, compiled=compiled)
        mb_temporal = plan.run_bytes_per_superstep(shape, "temporal",
                                                   compiled)
        if plan.par_time >= 2:
            assert mb_temporal < mb_plain, \
                (f"temporal modeled bytes/superstep {mb_temporal} not below "
                 f"plain {mb_plain} at par_time={plan.par_time}")
        rows.append((f"run_temporal_{prog.ndim}d_r{prog.radius}",
                     t_temporal * 1e6,
                     _with_bytes(
                         f"mcells_per_s="
                         f"{cells * steps_t / t_temporal / 1e6:.1f};"
                         f"temporal_speedup_vs_plain="
                         f"{t_plain_t / t_temporal:.2f}x;"
                         f"model_bytes_per_superstep={mb_temporal};"
                         f"model_bytes_ratio_vs_plain="
                         f"{mb_temporal / mb_plain:.3f};"
                         f"{_acc_fields(cs_t, cells * steps_t / t_temporal)}",
                         cs_t.run, g)))

    B = 2
    gb = jnp.stack([ref.random_grid(prog, shape, seed=s) for s in range(B)])
    cs_b = sten.compile(shape, steps=steps, plan=plan, batch=B)
    t_loop = _time(lambda: [cs.run(gb[i]) for i in range(B)], reps=2)
    t_batch = _time(cs_b.run, gb, reps=2)
    rows.append((f"run_batched_b{B}_{prog.ndim}d_r{prog.radius}",
                 t_batch * 1e6,
                 _with_bytes(
                     f"mcells_per_s={B * cells * steps / t_batch / 1e6:.1f};"
                     f"batched_speedup_vs_loop={t_loop / t_batch:.2f}x;"
                     f"{_acc_fields(cs_b, B * cells * steps / t_batch)}",
                     cs_b.run, gb)))


def run(use_tuned=None, smoke=None):
    if use_tuned is None:
        use_tuned = os.environ.get("REPRO_BENCH_TUNED") == "1"
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE") == "1"
    backend = os.environ.get("REPRO_BENCH_BACKEND") or None
    rows = []
    if smoke:
        cases = [(2, (64, 256), (32, 128), "star", "clamp")]
        radii = (1,)
    else:
        cases = [(2, (256, 512), (64, 128), "star", "clamp"),
                 (3, (32, 64, 256), (8, 16, 128), "star", "clamp")]
        radii = (1, 2, 4)
    programs = []
    for ndim, shape, block, pshape, boundary in cases:
        for rad in radii:
            programs.append((StencilProgram(ndim=ndim, radius=rad,
                                            shape=pshape, boundary=boundary),
                             shape, block))
    if not smoke:
        # non-star coverage through the identical lowering
        programs.append((StencilProgram(ndim=2, radius=1, shape="box",
                                        boundary="periodic"),
                         (256, 512), (64, 128)))

    for prog, shape, block in programs:
        cells = 1
        for s in shape:
            cells *= s

        if use_tuned:
            tuned = _tuned_plan(prog, shape)
            plan1 = BlockPlan(spec=prog, block_shape=tuned.block_shape,
                              par_time=1)
            plan2 = tuned
        else:
            plan1 = BlockPlan(spec=prog, block_shape=block, par_time=1)
            plan2 = BlockPlan(spec=prog, block_shape=block, par_time=2)
        steps = plan2.par_time
        cs1 = repro.stencil(prog).compile(shape, steps=steps, plan=plan1,
                                          backend=backend)
        cs2 = repro.stencil(prog).compile(shape, steps=steps, plan=plan2,
                                          backend=backend)
        g = ref.random_grid(prog, shape, seed=0)

        t1 = _time(cs1.run, g)
        t2 = _time(cs2.run, g)
        mcells = cells * steps / t2 / 1e6
        tag = f"kernel_{prog.ndim}d_r{prog.radius}"
        if prog.shape != "star":
            tag += f"_{prog.shape}_{prog.boundary}"
        rows.append((
            tag, t2 * 1e6,
            _with_bytes(
                f"mcells_per_s={mcells:.1f};"
                f"tb_speedup_vs_pt1={t1 / t2:.2f}x;"
                f"verify_ms={_verify_ms(prog, plan2, shape):.3f};"
                f"{_acc_fields(cs2, cells * steps / t2)}",
                cs2.run, g)))

    # executor comparisons ride the direct pallas path, so the
    # REPRO_BENCH_BACKEND pin does not apply to them; in smoke mode they
    # always run (tiny grid) — the regression gate needs the fused /
    # pipelined / batched rows in every CI artifact — while full runs keep
    # the historical default-backend-only guard.
    if (smoke or backend is None) and \
            variant_of("pallas-interpret", "pipelined"):
        prog, shape, block = programs[0]
        plan = BlockPlan(spec=prog, block_shape=block, par_time=2)
        _executor_rows(prog, shape, plan, rows)
    return rows
