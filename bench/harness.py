"""Run one cell once: set up, measure a window of whole calls, check, report.

The timed path is the users' front door with its defaults:
``repro.stencil(program, coeffs).compile(grid, steps=S[, devices=N])``, then
``.run(grid, S)`` once per call, each call's output the next call's input.
On a TPU the compiled ``pallas-tpu`` family must have been chosen; a host
without a TPU, or with fewer chips than the cell asks for, is an error.

Set-up (``setup_s``) runs from process start to the window's start: the
imports and the chip's start-up, the plan, the grid from the seed, and one
warm-up call of the cell's own shape (compiled, or loaded from the
persistent cache).  The window is a run of whole calls: it ends at the first
call that completes after ``--seconds``, and rates divide all completed work
by the time to that call's end.  Executables resolved inside the window are
counted; there should be none.

Correctness compares what the timed calls produced with the plain reference
(``bench/references/``), once the window has closed and the chip's peak
memory has been read.  It checks the window's last 1 to ``K`` calls
(``"checked": K`` in the cell's limits file), so that the reference, which
runs slower than the program, stays shorter than the window: the input of
every K-th call, counted from a phase drawn from the seed, is kept (copied
first where the call donates it), and the reference starts from the last
one kept.  Each number compared is printed beside its limit.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import statistics
import sys
import time
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import counts, gen, spec, xplane as tr

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` if
    set, else the fixed ``<checkout>/bench/.cache/jax``; every program is
    cached, however quick its compile, so a second run compiles nothing."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = os.path.join(root, "bench", ".cache", "jax")
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts executables resolved (compiled or loaded from the persistent
    cache) while armed, and the cache's hits and misses throughout."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self._cache_event)

    def __call__(self, event, duration, **kwargs):
        if self.armed and event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def _cache_event(self, event, **kwargs):
        for k in self.cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                self.cache[k] += 1


@dataclasses.dataclass
class Setup:
    cell: spec.Cell
    seed: int
    steps: int                       # per call
    shape: tuple
    program: dict                    # the configuration's program keys
    coeffs: dict                     # {"center", "taps": [(offset, c)]}
    reference: object                # the configuration's reference module
    mesh: object                     # reference / generation mesh, or None
    sharding: object                 # the grid's sharding as generated
    entry: Callable                  # grid -> grid after ``steps`` steps
    x: object                        # current grid (after the warm-up)
    rows: List[int]                  # receiver rows read after each call
    devices: list
    spans: Dict[str, float]          # host-clock set-up spans (s)
    compiled: object = None          # the CompiledStencil, when one
    donates: bool = False            # the entry consumes its input


def _mesh(cell: spec.Cell, devices):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if cell.chips == 1:
        return None, jax.sharding.SingleDeviceSharding(devices[0])
    axes = tuple(cell.config["reference_mesh"])
    names = tuple(f"a{i}" for i in range(len(axes)))
    mesh = Mesh(np.array(devices[:cell.chips]).reshape(axes), names)
    return mesh, NamedSharding(mesh, P(*names))


def check_devices(cell: spec.Cell, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def front_door_entry(cell: spec.Cell, program_cfg: dict, coeffs: dict,
                     steps: int, spans: dict, require_tpu: bool):
    """Compile through ``repro.stencil(...).compile`` with its defaults."""
    import jax.numpy as jnp
    import repro
    from repro.core.program import ProgramCoeffs, StencilProgram

    program = StencilProgram(**program_cfg)
    by_offset = {tuple(o): c for o, c in coeffs["taps"]}
    dtype = jnp.dtype(program.dtype)
    pc = ProgramCoeffs(
        center=jnp.asarray(coeffs["center"], dtype),
        taps=jnp.asarray([by_offset[tuple(o)] for o in
                          program.neighbor_taps], dtype))
    kw = {} if cell.chips == 1 else {"devices": cell.chips}
    t = time.perf_counter()
    cs = repro.stencil(program, coeffs=pc).compile(
        tuple(cell.config["grid"]), steps=steps, **kw)
    spans["plan"] = time.perf_counter() - t
    log(f"{cell.name}: {cs!r}")
    if require_tpu and not (cs.backend.startswith("pallas-tpu")
                            and cs.interpret is False):
        raise AssertionError(f"{cell.name}: ran on {cs.backend} "
                             f"interpret={cs.interpret}, not the compiled "
                             f"pallas-tpu family")
    return cs, (lambda g: cs.run(g, steps))


def setup(cell: spec.Cell, seed: int, *, require_tpu: bool = True,
          make_entry: Optional[Callable] = None) -> Setup:
    """Everything before the window.  ``make_entry(setup_fields)`` replaces
    the front door (the control does this); by default the front door."""
    import jax
    devices = check_devices(cell, require_tpu)
    program_cfg = dict(cell.config["program"])
    steps = int(cell.traffic["steps_per_call"][str(program_cfg["ndim"])])
    shape = tuple(cell.config["grid"])
    reference = spec.reference(cell.config)
    coeffs = gen.coefficients(
        reference.offsets(program_cfg["shape"], program_cfg["ndim"],
                          program_cfg["radius"]), seed)
    mesh, sharding = _mesh(cell, devices)
    spans: Dict[str, float] = {}
    compiled = None
    if make_entry is None:
        compiled, entry = front_door_entry(cell, program_cfg, coeffs, steps,
                                           spans, require_tpu)
        dtype = program_cfg["dtype"]
    else:
        entry, dtype = make_entry(program_cfg=program_cfg, coeffs=coeffs,
                                  steps=steps, mesh=mesh, spans=spans)
    x = jax.block_until_ready(gen.grid(shape, dtype, seed, sharding))
    readout = cell.traffic.get("receiver_rows", 0)
    rows = gen.receiver_rows(seed, shape[0], readout) if readout else []
    t = time.perf_counter()
    x = entry(x)
    for r in rows:
        np.asarray(x[r])
    x = jax.block_until_ready(x)
    spans["first_call"] = time.perf_counter() - t
    return Setup(cell=cell, seed=seed, steps=steps, shape=shape,
                 program=program_cfg, coeffs=coeffs, reference=reference,
                 mesh=mesh, sharding=sharding, entry=entry, x=x, rows=rows,
                 devices=devices[:cell.chips], spans=spans,
                 compiled=compiled,
                 donates=bool(compiled is not None and compiled.donate
                              and compiled.devices > 1))


@dataclasses.dataclass
class Window:
    calls: int
    durations: List[float]
    parts: List[tuple]               # per call, (dispatch, read, block) s
    seconds: float
    readouts: List[np.ndarray]       # per call, the receiver rows
    held: object                     # input of the first checked call
    held_index: int                  # its call index (1 = the first)
    out: object
    compiles: int


def run_window(s: Setup, seconds: float, counter: CompileCounter,
               calls: Optional[int] = None) -> Window:
    """Whole calls until the first that completes after ``seconds`` (or,
    given ``calls``, exactly that many)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    # a donated input is gone after its call: keep a copy of it instead
    keep = jnp.copy if s.donates else (lambda a: a)
    every = int(s.cell.limits["checked"])
    phase = int(np.random.default_rng(gen._words(s.seed, 3)).integers(every))
    # window calls are 1..n; the first input is the warm-up call's output
    held, held_index = keep(s.x), 1
    x, durations, parts, readouts = s.x, [], [], []
    s.x = None
    counter.count, counter.armed = 0, True
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            i = len(durations) + 1
            c0 = time.perf_counter()
            with TraceAnnotation("bench.call"):
                if (i + phase) % every == 0:
                    held, held_index = keep(x), i
                with TraceAnnotation("bench.dispatch"):
                    x = s.entry(x)
                c_read = time.perf_counter()
                if s.rows:
                    with TraceAnnotation("bench.receiver_read"):
                        readouts.append(np.stack(
                            [np.asarray(x[r]) for r in s.rows]))
                c_block = time.perf_counter()
                with TraceAnnotation("bench.block"):
                    x = jax.block_until_ready(x)
            c1 = time.perf_counter()
            durations.append(c1 - c0)
            parts.append((c_read - c0, c_block - c_read, c1 - c_block))
            if (len(durations) >= calls if calls is not None
                    else c1 - t0 >= seconds):
                break
    counter.armed = False
    return Window(calls=len(durations), durations=durations, parts=parts,
                  seconds=c1 - t0, readouts=readouts, held=held,
                  held_index=held_index, out=x, compiles=counter.count)


def call_profile(w: Window, longest: int = 5) -> str:
    """The window's call times on one line: quantiles, the time the calls
    spent over the median, and the longest calls split into dispatch,
    receiver read and block."""
    ms = [1e3 * d for d in w.durations]
    med = statistics.median(ms)
    over = [m - med for m in ms if m > 1.05 * med]
    top = sorted(range(len(ms)), key=lambda i: -ms[i])[:longest]
    split = "; ".join(
        f"#{i + 1} {ms[i]:.2f} = " + " + ".join(
            f"{k} {1e3 * v:.2f}" for k, v in
            zip(("dispatch", "read", "block"), w.parts[i]))
        for i in top)
    return (f"calls (ms): min {min(ms):.3f} median {med:.3f} "
            f"p95 {_p95(ms):.3f} max {max(ms):.3f}; "
            f"{len(over)} calls over 1.05 x median hold "
            f"{sum(over) / 1e3:.3f} s beyond it; longest: "
            f"{split}")


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def check(s: Setup, w: Window) -> Dict[str, dict]:
    """The reference over the checked span; each number with its limit."""
    import jax
    import jax.numpy as jnp
    advance = s.reference.advance_fn(s.program, s.coeffs, mesh=s.mesh)
    ref = jax.device_put(w.held.astype(s.program["dtype"]), s.sharding)
    w.held = None
    # calls held_index..n, each of s.steps steps
    ncalls = w.calls + 1 - w.held_index
    ref_rows = []
    if s.rows:
        for _ in range(ncalls):
            ref = advance(ref, s.steps)
            ref_rows.append(np.stack([np.asarray(ref[r]) for r in s.rows]))
    else:
        ref = advance(ref, ncalls * s.steps)
    out = jax.device_put(w.out, s.sharding) if s.mesh is not None else w.out
    err, scale = jax.jit(lambda a, b: (
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))),
        jnp.max(jnp.abs(b.astype(jnp.float32)))))(out, ref)
    scale = float(scale)
    limits = s.cell.limits["limits"]
    checks = {"grid_rel_err": {"value": float(err) / scale,
                               "limit": limits["grid_rel_err"]}}
    if s.rows:
        got = np.stack(w.readouts[-len(ref_rows):]).astype(np.float64)
        want = np.stack(ref_rows).astype(np.float64)
        checks["receiver_rel_err"] = {
            "value": float(np.max(np.abs(got - want))) / scale,
            "limit": limits["receiver_rel_err"]}
    log(f"checked calls {w.held_index}..{w.calls} of the window")
    return checks


def is_correct(checks: Dict[str, dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())


def _p95(values: List[float]) -> float:
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=100, method="inclusive")[94]


def end_to_end(s: Setup, w: Window, setup_s: float) -> Dict[str, float]:
    work = counts.cells(s.cell.config) * s.steps * w.calls
    return {"gcells_per_s": work / w.seconds / 1e9,
            "call_p95_ms": 1e3 * _p95(w.durations),
            "setup_s": setup_s}


def per_layer(s: Setup, w: Window, trace, window_ns):
    """The cell's per-layer metrics that their reducers find something to
    read for, and the context the reducers were given."""
    import jax
    devices = sorted(trace.devices)[:s.cell.chips]
    ctx = SimpleNamespace(
        trace=trace, window=window_ns, chips=s.cell.chips, spans=s.spans,
        devices=devices,
        # the peaks of the chip traced (an unknown kind is an error)
        peak=counts.peaks(jax.devices()[0].device_kind) if devices else None,
        counts={"flops": counts.cells(s.cell.config) * s.steps * w.calls
                * counts.flops_per_cell(len(s.coeffs["taps"])),
                "bytes": counts.bytes_per_call(s.cell.config) * w.calls})
    out = {}
    for m in s.cell.per_layer:
        got = spec.reducer(m["name"])(ctx)
        if got is None:
            continue
        entry = dict(got) if isinstance(got, dict) else {"value": got}
        entry["value"] = float(entry["value"])
        entry["unit"] = m["unit"]
        out[m["name"]] = entry
    return out, ctx


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str = spec.ROOT,
        require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax
    devices = check_devices(cell, require_tpu)
    log(f"devices: {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); compile cache "
        f"{enable_compile_cache(root)}")
    counter = CompileCounter()
    s = setup(cell, seed, require_tpu=require_tpu)
    trace_dir = os.path.join(root, "bench", ".traces",
                             f"{cell.name}-{os.getpid()}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    w = run_window(s, seconds, counter)
    if trace:
        jax.profiler.stop_trace()
    if w.compiles:
        log(f"WARNING: {w.compiles} executables were compiled or loaded "
            f"inside the window")
    log(f"window: {w.calls} calls of {s.steps} steps in {w.seconds:.3f} s; "
        f"set-up {setup_s:.3f} s ({s.spans}); persistent cache "
        f"{counter.cache}")
    log(call_profile(w))
    peak = memory_peak(s.devices)
    compiled = s.compiled
    s.compiled = None
    del compiled
    gc.collect()
    t = time.perf_counter()
    checks = check(s, w)
    log(f"reference check: {time.perf_counter() - t:.3f} s")
    kind = devices[0]
    device = {"platform": kind.platform, "kind": kind.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    correct = is_correct(checks)
    result = {"correct": correct, "attempted": w.calls,
              "failed": 0 if correct else w.calls + 1 - w.held_index}
    if trace:
        tr_obj = tr.load(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        window_ns = tr_obj.window()
        metrics, ctx = per_layer(s, w, tr_obj, window_ns)
        if ctx.devices and window_ns:
            busy = [tr.busy_ns(tr_obj, d, window_ns) for d in ctx.devices]
            device["busy_s"] = sum(busy) / len(busy) / 1e9
            device["window_s"] = (window_ns[1] - window_ns[0]) / 1e9
            result["breakdown"] = tr.breakdown(tr_obj, ctx.devices,
                                               window_ns)
    else:
        e2e = end_to_end(s, w, setup_s)
        metrics = {m["name"]: {"value": e2e[spec.quantity(m["name"])],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result


def report(result: dict) -> None:
    """Each number compared beside its limit, as the last lines of standard
    error; the result as the last line of standard output."""
    for name, v in result["checks"].items():
        print(f"check {name} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
