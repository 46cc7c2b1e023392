"""Program spans on the profiler's clock, and the compile phases inside them.

Every ``repro.obs`` span, recorded or not, is a ``jax.profiler.
TraceAnnotation`` named ``repro.<name>`` while a profiler records
(:class:`Span`), so a device trace shows the program's own host time — the
front door's validation, the caller copy, the executor's launch — beside
the ops it enqueued.  A span also marks its thread as inside the program
for its extent; with nothing to record or annotate, that is all the shared
:data:`INSIDE` does.

One ``jax.monitoring`` time-span listener sums JAX's compile phases —
tracing to a jaxpr, lowering to MLIR (the Pallas/Mosaic kernels are built
there) and the backend compile or persistent-cache load — that run on a
thread inside a span, into process-wide totals (:func:`compile_totals`).
Events nest: a nested jit reports its own trace inside its caller's, and
eager work or the Pallas interpreter trace inside a lowering.  Each
instant counts once, for the innermost event that holds it (an event
arrives when it ends, so after the events it holds): nested events of one
phase count as their union, and the three phases sum to the union of all.

JAX is imported by the first span, not by this module.
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Dict, List

PREFIX = "repro."

#: JAX's compile events (``jax._src.dispatch``) -> the totals' keys.
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}

_tls = threading.local()
_lock = threading.Lock()
_annotation = None          # jax.profiler.TraceAnnotation, once loaded


class _Union:
    """Disjoint, sorted intervals."""

    __slots__ = ("starts", "ends")

    def __init__(self):
        self.starts: List[float] = []
        self.ends: List[float] = []

    def add(self, a: float, b: float) -> float:
        """Add ``[a, b]``; returns the length it newly covers."""
        lo = bisect.bisect_left(self.ends, a)
        hi = bisect.bisect_right(self.starts, b)
        if lo < hi:
            a, b = min(a, self.starts[lo]), max(b, self.ends[hi - 1])
        new = (b - a) - sum(e - s for s, e in
                            zip(self.starts[lo:hi], self.ends[lo:hi]))
        self.starts[lo:hi] = [a]
        self.ends[lo:hi] = [b]
        return new


_covered = _Union()
_totals: Dict[str, float] = {k: 0.0 for k in PHASES.values()}
_executables = 0


def _on_phase(event: str, start: float, end: float, **_) -> None:
    global _executables
    key = PHASES.get(event)
    if key is None or not getattr(_tls, "depth", 0):
        return
    with _lock:
        _totals[key] += _covered.add(start, end)
        if key == "backend_s":
            _executables += 1


def profiling() -> bool:
    """Whether a profiler records host events now.  The first call loads
    JAX's profiler and registers the compile-phase listener; later calls
    are ``TraceAnnotation.is_enabled`` itself."""
    global _annotation, profiling
    with _lock:
        if _annotation is None:
            import jax.monitoring
            import jax.profiler
            jax.monitoring.register_event_time_span_listener(_on_phase)
            _annotation = jax.profiler.TraceAnnotation
            profiling = _annotation.is_enabled
    return _annotation.is_enabled()


class _NullSpan:
    """The span opened inside a JAX trace: a shared, stateless, reusable
    no-op (a traced call must not annotate or time trace-time work)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class _Inside:
    """The span that neither records nor is profiled: it only counts its
    thread in.  Shared and stateless, so the common case allocates
    nothing."""

    __slots__ = ()

    def __enter__(self):
        _tls.depth = getattr(_tls, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.depth -= 1
        return False

    def set(self, **attrs) -> "_Inside":
        return self


INSIDE = _Inside()


class Span:
    """A program span: counts its thread in for its extent, is a
    ``repro.<name>`` annotation if a profiler is recording when it opens
    (one that starts later would not record it either), and — when
    ``rec`` is a recorder — emits one ``span`` event with its host-clock
    duration when it exits.

    ``set(**attrs)`` attaches attributes mid-flight (kept only when
    recording).
    """

    __slots__ = ("_rec", "name", "attrs", "_t0", "_ann", "dur_s")

    def __init__(self, rec, name: str, attrs: dict):
        self._rec = rec
        self.name = name
        self.attrs = attrs
        self.dur_s = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        _tls.depth = getattr(_tls, "depth", 0) + 1
        self._ann = None
        if profiling():
            self._ann = _annotation(PREFIX + self.name)
            self._ann.__enter__()
        if self._rec is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._rec is not None:
            self.dur_s = time.perf_counter() - self._t0
            ev = {"type": "span", "name": self.name, "dur_s": self.dur_s}
            if exc_type is not None:
                ev["error"] = exc_type.__name__
            ev.update(self.attrs)
            self._rec.emit(ev)
        _tls.depth -= 1
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        return False


def compile_totals() -> dict:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache) inside program spans since the last
    :func:`reset`, and the executables compiled or loaded there."""
    with _lock:
        out = dict(_totals)
        out["executables"] = _executables
    return out


def reset() -> None:
    global _covered, _executables
    with _lock:
        _covered = _Union()
        for k in _totals:
            _totals[k] = 0.0
        _executables = 0
