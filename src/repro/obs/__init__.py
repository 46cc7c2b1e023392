"""repro.obs — the flight recorder: program spans, compile phases, run metrics.

Every instrumented path — ``executor.compile``/``run``, the caller copy
and the executor's launch, the serving front's flush — opens a span.
While a profiler records, a span is a ``jax.profiler.TraceAnnotation``
named ``repro.<name>`` (``obs/profiler.py``), on the same clock as the
device's ops; otherwise it only marks its thread as inside the program
(about a microsecond).  Spans never block and never compile: a run
span covers validation and what the call enqueued, not its completion.
Spans open only outside a JAX trace.

JAX's own compile phases inside those spans — tracing, lowering to MLIR
(Pallas kernels built), backend compile or persistent-cache load — are
summed process-wide and always on: :func:`compile_totals`.

Recording — host-clock ``span`` events, counters, value streams, and
predicted-vs-achieved accuracy samples (the tuner's measurement harness
times candidates honestly and files one per candidate in a
schema-versioned history ledger the calibration layer, ROADMAP item 3,
can later fit from) — is off by default.  ``REPRO_OBS=1`` (or an active
:func:`profile` scope) turns it on; when off, the counter and event
helpers short-circuit after one dict lookup (the overhead guard in
tests/test_obs.py bounds a span's cost at <2% of a fused smoke run).

Usage::

    import repro, repro.obs

    with repro.obs.profile() as rec:
        cs = repro.stencil(program).compile((256, 1024), steps=8)
        out = cs.run(grid)
    rec.spans("run")[0]["dur_s"]       # host time of the call's dispatch
    repro.obs.compile_totals()         # {"trace_s", "lower_s", ...}

Env:
    REPRO_OBS          1/true enables the global recorder (default off)
    REPRO_OBS_JSONL    stream every event to this JSONL file
    REPRO_OBS_HISTORY  accuracy-sample ledger (default obs/history.jsonl;
                       empty string disables the ledger)

``python -m repro.obs report`` renders the human summary (per-backend
accuracy distribution, slowest spans, plan-cache hit rates); ``--json``
emits the same machine-readably for CI.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Optional

from repro.obs.history import (DEFAULT_HISTORY_PATH, SCHEMA_VERSION,
                               append_sample, default_history_path,
                               read_history)
from repro.obs import profiler
from repro.obs.profiler import NULL_SPAN, Span, compile_totals
from repro.obs.recorder import Recorder, percentile

__all__ = [
    "NULL_SPAN",
    "Recorder",
    "SCHEMA_VERSION",
    "Span",
    "active",
    "append_sample",
    "compile_totals",
    "count",
    "enabled",
    "event",
    "observe",
    "percentile",
    "profile",
    "read_history",
    "record_accuracy",
    "reset",
    "span",
]

ENV_SWITCH = "REPRO_OBS"
_OFF = frozenset(("", "0", "false", "off", "no"))

# One slot each so toggles are atomic swaps; the lock only guards lazy
# construction of the env-driven recorder (profile() swaps are per-call).
# ``env_off`` caches the REPRO_OBS decision (environ lookups are too slow
# for per-call-site checks); :func:`reset` re-reads it.
_state = {"override": None, "env_recorder": None, "env_off": None}
_state_lock = threading.Lock()


def active() -> Optional[Recorder]:
    """The recorder every module-level helper routes to, or None when off.

    A :func:`profile` scope (or :func:`enable`) wins over the environment;
    otherwise ``REPRO_OBS`` decides — read once per process (:func:`reset`
    re-reads, for tests) — with the env-driven recorder built lazily on
    first use (JSONL/history sinks from ``REPRO_OBS_JSONL`` /
    ``REPRO_OBS_HISTORY``).
    """
    rec = _state["override"]
    if rec is not None:
        return rec
    off = _state["env_off"]
    if off is None:
        off = os.environ.get(ENV_SWITCH, "0").strip().lower() in _OFF
        _state["env_off"] = off
    if off:
        return None
    rec = _state["env_recorder"]
    if rec is None:
        with _state_lock:
            rec = _state["env_recorder"]
            if rec is None:
                rec = Recorder(
                    jsonl_path=os.environ.get("REPRO_OBS_JSONL") or None,
                    history_path=default_history_path())
                _state["env_recorder"] = rec
    return rec


def enabled() -> bool:
    return active() is not None


def enable(recorder: Optional[Recorder] = None) -> Recorder:
    """Force recording on for this process (until :func:`disable`)."""
    rec = recorder if recorder is not None else Recorder()
    _state["override"] = rec
    return rec


def disable() -> None:
    """Drop any programmatic override (the env switch still applies)."""
    _state["override"] = None


def reset() -> None:
    """Forget the override, the env-driven recorder, the cached
    ``REPRO_OBS`` decision and the compile totals (test isolation / env
    re-reads)."""
    profiler.reset()
    _state["override"] = None
    _state["env_off"] = None
    rec = _state["env_recorder"]
    _state["env_recorder"] = None
    if rec is not None:
        rec.close()


@contextlib.contextmanager
def profile(jsonl_path: Optional[str] = None,
            history_path: Optional[str] = None):
    """Record everything inside the scope into a fresh :class:`Recorder`.

    The yielded recorder becomes the process-global target for the scope
    (nesting restores the previous one), so ``with repro.obs.profile() as
    rec:`` observes any instrumented code it wraps regardless of
    ``REPRO_OBS``.  Sinks default to in-memory only — pass ``jsonl_path`` /
    ``history_path`` to persist.
    """
    rec = Recorder(jsonl_path=jsonl_path, history_path=history_path)
    prev = _state["override"]
    _state["override"] = rec
    try:
        yield rec
    finally:
        _state["override"] = prev
        rec.close()


# -- module-level instrumentation helpers (no-ops when disabled) -------------

def span(name: str, **attrs):
    """The ``repro.<name>`` program span, recorded when recording is on
    (:class:`Span`; the shared ``profiler.INSIDE`` when there is nothing
    to record or annotate)."""
    rec = active()
    if rec is None and not profiler.profiling():
        return profiler.INSIDE
    return Span(rec, name, attrs)


def event(name: str, **attrs) -> None:
    rec = active()
    if rec is not None:
        rec.event(name, **attrs)


def count(name: str, n: int = 1) -> None:
    rec = active()
    if rec is not None:
        rec.count(name, n)


def observe(name: str, value: float) -> None:
    rec = active()
    if rec is not None:
        rec.observe(name, value)


def record_accuracy(**fields) -> Optional[dict]:
    rec = active()
    return None if rec is None else rec.record_accuracy(**fields)
