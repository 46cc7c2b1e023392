"""Flight recorder (repro.obs): switch semantics, instrumentation, telemetry.

Covers the observability contract end to end:

  * recording off by default — the helpers record nothing, instrumented
    paths write no files, and a span (a profiler annotation even when
    off) costs <2% of a fused smoke run (the overhead guard);
  * program spans on the profiler's clock: ``repro.run`` holds the
    caller copy and the launch in a ``jax.profiler`` trace, with obs off;
  * ``profile()`` around the front door yields ``compile``/``run`` spans
    on both the pallas-interpret and xla-reference backends, and the run
    span never blocks;
  * the compile-phase totals: counted inside program spans only, each
    phase the union of its (nested) intervals;
  * the serving front's recorder-backed stats (compile/run seconds split,
    latency percentiles, queue depth, batch occupancy);
  * the tuner's measurement harness recording skip stage + exception
    class, and filing accuracy samples in the history ledger;
  * trace-counter accounting staying consistent under concurrent
    compiles;
  * the ``python -m repro.obs report`` summary (human + ``--json``).
"""

import json
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

import repro
from repro import obs
from repro.core import reference as ref
from repro.core.program import StencilProgram
from repro.kernels import common


@pytest.fixture(autouse=True)
def _obs_isolation(monkeypatch):
    """Every test starts with the recorder off and no env spillover."""
    for var in ("REPRO_OBS", "REPRO_OBS_JSONL", "REPRO_OBS_HISTORY"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    yield
    obs.reset()


def _smoke_compiled(backend=None, **kwargs):
    prog = StencilProgram(ndim=2, radius=1)
    cs = repro.stencil(prog).compile((16, 128), steps=2, plan="model",
                                     max_par_time=2, backend=backend,
                                     **kwargs)
    grid = ref.random_grid(prog, (16, 128), seed=0)
    return cs, grid


# ---- switch semantics -------------------------------------------------------

def test_disabled_by_default_helpers_are_noops():
    assert obs.active() is None
    assert not obs.enabled()
    from repro.obs import profiler
    # with no profiler recording either, a span only counts its thread in
    with obs.span("anything", a=1) as sp:
        assert sp.set(k=2) is sp
        assert profiler._tls.depth == 1
    assert profiler._tls.depth == 0
    # the shared no-op (inside a trace) is reusable and inert
    with obs.NULL_SPAN as sp:
        assert sp.set(k=2) is sp
    obs.event("e", x=1)
    obs.count("c", 3)
    obs.observe("s", 0.5)
    assert obs.record_accuracy(model_accuracy=1.0) is None


def test_env_off_values_disable(monkeypatch):
    for off in ("0", "false", "off", "no", ""):
        monkeypatch.setenv("REPRO_OBS", off)
        obs.reset()
        assert obs.active() is None
    monkeypatch.setenv("REPRO_OBS", "1")
    obs.reset()
    assert obs.active() is not None


def test_obs_off_emits_nothing(tmp_path, monkeypatch):
    """REPRO_OBS=0: instrumented compile+run leave no events and no files."""
    monkeypatch.setenv("REPRO_OBS", "0")
    monkeypatch.setenv("REPRO_OBS_JSONL", str(tmp_path / "events.jsonl"))
    monkeypatch.setenv("REPRO_OBS_HISTORY", str(tmp_path / "history.jsonl"))
    obs.reset()
    cs, grid = _smoke_compiled()
    jax.block_until_ready(cs.run(grid))
    assert obs.active() is None
    assert not (tmp_path / "events.jsonl").exists()
    assert not (tmp_path / "history.jsonl").exists()


def test_profile_overrides_env_and_restores(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "0")
    obs.reset()
    with obs.profile() as rec:
        assert obs.active() is rec
        obs.count("inside")
        assert rec.counter("inside") == 1
    assert obs.active() is None


# ---- recorder primitives ----------------------------------------------------

def test_recorder_counters_samples_percentiles():
    rec = obs.Recorder()
    for v in (1.0, 2.0, 3.0, 4.0, 10.0):
        rec.observe("lat", v)
    rec.count("n")
    rec.count("n", 4)
    assert rec.counter("n") == 5
    assert rec.sample_sum("lat") == 20.0
    assert rec.percentile("lat", 50) == 3.0
    ps = rec.percentiles("lat")
    assert set(ps) == {"p50", "p95", "p99"}
    assert ps["p99"] == 10.0
    assert obs.percentile([], 99) == 0.0


def test_recorder_jsonl_sink_and_counter_flush(tmp_path):
    path = tmp_path / "sub" / "events.jsonl"
    rec = obs.Recorder(jsonl_path=str(path))
    with rec.span("work", tag="t") as sp:
        sp.set(extra=1)
    rec.count("c", 2)
    rec.close()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines[0]["type"] == "span"
    assert lines[0]["name"] == "work"
    assert lines[0]["extra"] == 1
    assert lines[0]["dur_s"] >= 0
    assert lines[-1] == {"type": "counter", "counters": {"c": 2},
                         "ts": lines[-1]["ts"]}


def test_span_records_error_class():
    rec = obs.Recorder()
    with pytest.raises(RuntimeError):
        with rec.span("boom"):
            raise RuntimeError("x")
    assert rec.spans("boom")[0]["error"] == "RuntimeError"


# ---- executor instrumentation ----------------------------------------------

@pytest.mark.parametrize("backend", ["pallas-interpret", "xla-reference"])
def test_profile_around_fused_run_records_nonblocking_spans(backend,
                                                           monkeypatch,
                                                           tmp_path):
    history = tmp_path / "history.jsonl"
    blocked = []
    real_block = jax.block_until_ready
    with obs.profile(history_path=str(history)) as rec:
        cs, grid = _smoke_compiled(backend=backend)
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: blocked.append(x) or real_block(x))
        out = cs.run(grid)
        monkeypatch.setattr(jax, "block_until_ready", real_block)
    assert blocked == []
    # results are unchanged by instrumentation
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(cs.run(grid)), rtol=1e-6, atol=1e-6)

    (compile_span,) = rec.spans("compile")
    assert compile_span["plan_source"] == "model"
    assert compile_span["backend"].startswith(backend + "@")
    assert compile_span["model_bytes_per_superstep"] > 0
    assert compile_span["cache_hit"] is False
    assert not any(k.startswith("xla_") for k in compile_span)
    assert rec.counter("compile.plan_cache_miss") == 1

    (run_span,) = rec.spans("run")
    assert run_span["backend"].startswith(backend + "@")
    assert run_span["predicted_gbps"] > 0
    assert run_span["dur_s"] > 0
    # a run span is the dispatch's host time, not a throughput reading
    assert "achieved_gbps" not in run_span
    assert rec.accuracy_samples() == []
    assert not history.exists()


def _host_spans(trace_dir):
    """The ``repro.*`` host events of a ``jax.profiler`` trace, as
    (name, start_ns, end_ns), in start order."""
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("backend", ["pallas-interpret", "xla-reference"])
def test_run_span_holds_copy_and_launch_on_the_profiler_clock(backend,
                                                              tmp_path):
    """With recording off, each call is a ``repro.run`` annotation on the
    profiler's clock holding the executor's own host work."""
    cs, grid = _smoke_compiled(backend=backend)
    jax.block_until_ready(cs.run(grid))           # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = cs.run(grid)
    finally:
        jax.profiler.stop_trace()
    jax.block_until_ready(out)
    spans = _host_spans(tmp_path)
    names = [n for n, _, _ in spans]
    want = (["repro.run", "repro.copy", "repro.launch"]
            if backend == "pallas-interpret" else
            ["repro.run", "repro.launch"])
    assert names == want
    (_, r0, r1), *inner = spans
    assert all(r0 <= a <= b <= r1 for _, a, b in inner)
    assert obs.active() is None


def test_compile_totals_count_the_warmup_inside_spans_only():
    """The warm-up call's ``run_call`` and caller copy are traced, lowered
    and compiled once each inside ``repro.run``; later calls and a jit
    outside the program's spans add nothing."""
    prog = StencilProgram(ndim=2, radius=1)
    shape = (40, 256)          # a shape no other test compiles
    cs = repro.stencil(prog).compile(shape, steps=3, plan="model",
                                     max_par_time=2)
    grid = ref.random_grid(prog, shape, seed=0)
    assert obs.compile_totals() == {"trace_s": 0.0, "lower_s": 0.0,
                                    "backend_s": 0.0, "executables": 0}
    t0 = time.time()
    jax.block_until_ready(cs.run(grid))
    wall = time.time() - t0
    warm = obs.compile_totals()
    assert warm["executables"] == 2          # run_call and the copy
    for key in ("trace_s", "lower_s", "backend_s"):
        assert 0 < warm[key]
    assert warm["trace_s"] + warm["lower_s"] + warm["backend_s"] <= wall
    jax.block_until_ready(cs.run(grid))
    jax.block_until_ready(jax.jit(lambda g: g * 3 + 1)(grid))
    assert obs.compile_totals() == warm
    obs.reset()
    assert obs.compile_totals()["executables"] == 0


def test_compile_totals_sum_the_union_of_nested_events():
    from repro.obs import profiler
    trace = "/jax/core/compile/jaxpr_trace_duration"
    profiler._on_phase(trace, 0.0, 50.0)          # outside any span
    with obs.span("compile"):
        profiler._on_phase(trace, 2.0, 3.0)       # nested jits report first
        profiler._on_phase(trace, 4.0, 6.0)
        profiler._on_phase(trace, 1.0, 10.0)      # the enclosing trace
        profiler._on_phase(trace, 8.0, 12.0)      # overlaps its end
        profiler._on_phase(trace, 20.0, 21.0)
        profiler._on_phase("/jax/other", 0.0, 99.0)
        profiler._on_phase("/jax/core/compile/backend_compile_duration",
                           30.0, 30.5)
        # a lowering that holds a trace counts only the time around it
        profiler._on_phase("/jax/core/compile/jaxpr_to_mlir_module_duration",
                           19.0, 24.0)
    totals = obs.compile_totals()
    assert totals["trace_s"] == pytest.approx(12.0)
    assert totals["lower_s"] == pytest.approx(4.0)
    assert totals["backend_s"] == pytest.approx(0.5)
    assert totals["executables"] == 1


def test_jitted_run_does_not_record():
    """A jitted wrapper around an instrumented entry must not emit run
    spans traced into the executable (the trace guard)."""
    with obs.profile() as rec:
        cs, grid = _smoke_compiled(backend="xla-reference")
        n_before = len(rec.spans("run"))
        fn = jax.jit(lambda g: cs.run(g))
        jax.block_until_ready(fn(grid))
        jax.block_until_ready(fn(grid))
        assert len(rec.spans("run")) == n_before


def test_disabled_overhead_guard_under_two_percent():
    """Recording off, a span (its profiler annotation included) and a
    counter must cost <2% of a fused smoke run even if every
    instrumentation site fired on every call (16 sites is far above the
    real count on the run path — run() opens three spans)."""
    prog = StencilProgram(ndim=2, radius=1)
    cs = repro.stencil(prog).compile((64, 512), steps=4, plan="model",
                                     max_par_time=2)
    grid = ref.random_grid(prog, (64, 512), seed=0)
    jax.block_until_ready(cs.run(grid))           # warm the executable
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        jax.block_until_ready(cs.run(grid))
    run_s = (time.perf_counter() - t0) / reps

    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("site"):
            pass
        obs.count("site")
    per_site = (time.perf_counter() - t0) / n
    assert per_site * 16 < 0.02 * run_s, (
        f"disabled obs costs {per_site * 1e9:.0f} ns/site vs "
        f"{run_s * 1e3:.2f} ms smoke run")


# ---- trace-counter accounting ----------------------------------------------

def test_trace_counts_thread_safe_and_snapshotted():
    common.reset_trace_counts()
    threads = [threading.Thread(
        target=lambda: [common._note_trace("obs_test") for _ in range(2000)])
        for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert common.trace_count("obs_test") == 8 * 2000
    snap = common.trace_counts()
    assert snap["obs_test"] == 8 * 2000
    # snapshots are copies, not views
    snap["obs_test"] = 0
    assert common.trace_count("obs_test") == 8 * 2000
    common.reset_trace_counts()
    assert common.trace_count("obs_test") == 0


def test_concurrent_compiles_keep_counters_consistent():
    """Concurrent front-door compiles (each tracing its executable) must
    not lose trace-count increments or corrupt recorder state."""
    common.reset_trace_counts()
    prog = StencilProgram(ndim=2, radius=1)
    # a shape no other test compiles, so the executable really traces here
    shape = (24, 384)
    grid = ref.random_grid(prog, shape, seed=0)
    errors = []

    def compile_and_run(seed):
        try:
            cs = repro.stencil(prog).compile(
                shape, steps=2, plan="model", max_par_time=2)
            jax.block_until_ready(cs.run(grid))
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    with obs.profile() as rec:
        threads = [threading.Thread(target=compile_and_run, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors
    assert len(rec.spans("run")) == 4
    assert rec.counter("compile.plan_cache_miss") == 4
    # all four runs share one executable: at least one trace, at most one
    # per thread
    assert 1 <= common.trace_count("run_call") <= 4


# ---- history ledger + report CLI -------------------------------------------

def test_history_ledger_schema_and_report(tmp_path):
    history = tmp_path / "history.jsonl"
    events = tmp_path / "events.jsonl"
    with obs.profile(jsonl_path=str(events),
                     history_path=str(history)) as rec:
        with rec.span("compile", backend="b@1", cache_hit=True):
            pass
        rec.count("compile.plan_cache_hit")
        for acc in (0.5, 0.7):
            rec.record_accuracy(backend="pallas-interpret",
                                model_accuracy=acc, achieved_gbps=1.0,
                                predicted_gbps=1.0 / acc)
    # unparseable + foreign-schema lines are skipped, not fatal
    with open(history, "a") as f:
        f.write("not json\n")
        f.write(json.dumps({"schema": 999, "model_accuracy": 9.0}) + "\n")
    ledger = obs.read_history(str(history))
    assert [s["model_accuracy"] for s in ledger] == [0.5, 0.7]

    from repro.obs.report import render, summarize
    summary = summarize(str(history), events_path=str(events))
    dist = summary["history"]["backends"]["pallas-interpret"]
    assert dist["count"] == 2
    assert dist["mean"] == pytest.approx(0.6)
    assert summary["events"]["compile"]["cache_hit_rate"] == 1.0
    assert summary["events"]["counters"]["compile.plan_cache_hit"] == 1
    text = render(summary)
    assert "pallas-interpret" in text and "plan cache" in text

    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs", "report",
         "--history", str(history), "--events", str(events), "--json"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    loaded = json.loads(proc.stdout)
    assert loaded["history"]["samples"] == 2


def test_report_on_missing_history(tmp_path):
    from repro.obs.report import render, summarize
    summary = summarize(str(tmp_path / "absent.jsonl"))
    assert summary["history"]["samples"] == 0
    assert "no accuracy samples" in render(summary)


# ---- measurement harness skip recording ------------------------------------

def test_measure_files_an_accuracy_sample(tmp_path):
    from repro.tuning.measure import measure_candidate
    from repro.tuning.model_rank import predict
    from repro.tuning.space import enumerate_space

    prog = StencilProgram(ndim=2, radius=1)
    shape = (16, 128)
    cand = enumerate_space(prog, grid_shape=shape, max_par_time=2)[0]
    ranked = predict(prog, cand, grid_shape=shape)
    assert measure_candidate(prog, ranked, shape, reps=1).ok   # off: none
    history = tmp_path / "history.jsonl"
    with obs.profile(history_path=str(history)) as rec:
        m = measure_candidate(prog, ranked, shape, reps=1)
    assert m.ok
    (sample,) = rec.accuracy_samples()
    assert sample["source"] == "tuning.measure"
    assert sample["backend"] == cand.backend
    assert sample["model_accuracy"] == pytest.approx(m.model_accuracy)
    assert sample["achieved_gbps"] == pytest.approx(m.achieved_gbps)
    (ledger,) = obs.read_history(str(history))
    assert ledger["key"] == sample["key"]
    assert ledger["par_time"] == cand.plan.par_time


def test_measure_records_skip_stage_and_class(monkeypatch):
    from repro.tuning.measure import measure_candidate
    from repro.tuning.model_rank import predict
    from repro.tuning.space import enumerate_space

    prog = StencilProgram(ndim=2, radius=1)
    shape = (16, 128)
    cand = enumerate_space(prog, grid_shape=shape, max_par_time=2)[0]
    ranked = predict(prog, cand, grid_shape=shape)

    import repro.tuning.measure as measure_mod

    def broken_lower(*a, **k):
        raise RuntimeError("deliberate lowering failure")

    monkeypatch.setattr(measure_mod, "lower", broken_lower)
    with obs.profile() as rec:
        m = measure_candidate(prog, ranked, shape)
    assert not m.ok
    assert m.error_class == "RuntimeError"
    assert m.stage == "lower"
    assert "FAILED at lower" in m.describe()
    assert rec.counter("tuning.measure_skip") == 1
    assert rec.counter("tuning.measure_skip.RuntimeError") == 1
    (ev,) = [e for e in rec.events if e.get("name") == "measure_skip"]
    assert ev["stage"] == "lower"
    assert ev["error_class"] == "RuntimeError"


# ---- serving front telemetry ------------------------------------------------

def test_server_stats_split_and_latency():
    from repro.launch.stencil_serve import StencilServer

    prog = StencilProgram(ndim=2, radius=1)
    server = StencilServer(max_batch=4, max_par_time=2)
    rng = np.random.RandomState(0)
    rids = [server.submit(prog, rng.uniform(-1, 1, (20, 140)), steps=3)
            for _ in range(5)]
    results = server.flush()
    assert set(results) == set(rids) and not server.failed

    s = server.stats
    assert s.requests == 5
    assert s.batches == 2               # 4 + 1
    assert s.batched_requests == 4
    assert s.compile_seconds > 0        # both chunk shapes compiled cold
    assert s.run_seconds > 0            # the blocking pass always counts
    assert s.seconds == pytest.approx(s.compile_seconds + s.run_seconds)
    assert s.cell_steps == 5 * 20 * 140 * 3
    assert s.mcell_steps_per_s > 0

    rec = server.recorder
    assert rec.samples("serve.queue_depth") == [5.0]
    assert rec.samples("serve.batch_occupancy") == [1.0, 0.25]
    lat = s.latency_percentiles()
    assert len(rec.samples("serve.request_latency_s")) == 5
    assert 0 < lat["p50"] <= lat["p95"] <= lat["p99"]
    (flush_span,) = rec.spans("serve.flush")
    assert flush_span["requests"] == 5
    assert flush_span["results"] == 5
    assert flush_span["failed"] == 0

    # a second flush of the same shapes is warm: run time, no compile time
    compile_before = s.compile_seconds
    rid = server.submit(prog, rng.uniform(-1, 1, (20, 140)), steps=3)
    server.submit(prog, rng.uniform(-1, 1, (20, 140)), steps=3)
    server.submit(prog, rng.uniform(-1, 1, (20, 140)), steps=3)
    server.submit(prog, rng.uniform(-1, 1, (20, 140)), steps=3)
    out = server.flush()
    assert rid in out
    assert s.compile_seconds == compile_before
    assert s.requests == 9


def test_server_records_failures_and_identity_batches(monkeypatch):
    from repro import executor
    from repro.launch.stencil_serve import StencilServer

    prog = StencilProgram(ndim=2, radius=1)
    server = StencilServer(max_batch=4, max_par_time=2)
    rng = np.random.RandomState(1)
    ident = [server.submit(prog, rng.uniform(-1, 1, (20, 140)), steps=0)
             for _ in range(2)]
    bad = server.submit(prog, rng.uniform(-1, 1, (24, 130)), steps=2)

    def exploding(self, grid, steps=None):
        raise RuntimeError("deliberate failure")

    monkeypatch.setattr(executor.CompiledStencil, "run", exploding)
    results = server.flush()
    assert set(results) == set(ident)
    assert set(server.failed) == {bad}
    assert server.recorder.counter("serve.failed") == 1
    assert server.stats.batches == 1     # only the identity chunk ran
    assert server.stats.cell_steps == 0  # identity contributes no work
