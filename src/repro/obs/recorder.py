"""Process-local flight recorder: spans, counters, value streams, JSONL sink.

Importable without jax (stdlib only at import): the recorder must be
importable from every layer — kernels, executor, serving, benchmarks —
without creating cycles or adding a cold-start cost.  A span's annotation
on the profiler's clock imports jax on first use (``obs/profiler.py``).

A :class:`Recorder` is an append-only, thread-safe buffer of event dicts
(its spans are ``obs/profiler.py``'s :class:`Span`):

    span     — a timed region (``{"type": "span", "name", "dur_s", ...}``)
    event    — a point-in-time fact (``{"type": "event", ...}``)
    counter  — monotonic named counts (``{"type": "counter"}`` on close)
    accuracy — a predicted-vs-achieved throughput sample; additionally
               appended to the schema-versioned history file when the
               recorder carries a ``history_path`` (see history.py)

Every emit optionally streams a JSON line to ``jsonl_path`` so a crashed
run still leaves its trace on disk.  Whether any of this happens at all is
the *caller's* choice: module-level helpers in ``repro.obs`` route through
the global on/off switch (``REPRO_OBS``), while an explicitly constructed
``Recorder`` (e.g. the serving front's) always records.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

from repro.obs.profiler import Span


class Recorder:
    """Thread-safe in-memory event buffer with optional JSONL/history sinks.

    All mutation happens under one lock; reads return copies so callers can
    iterate while other threads keep recording.
    """

    def __init__(self, jsonl_path: Optional[str] = None,
                 history_path: Optional[str] = None):
        self._lock = threading.Lock()
        self.events: List[dict] = []
        self.counters: Dict[str, int] = collections.Counter()
        self._samples: Dict[str, List[float]] = {}
        self.jsonl_path = jsonl_path
        self.history_path = history_path
        self._jsonl = None
        self.t_start = time.time()

    # -- emission ------------------------------------------------------------

    def emit(self, event: dict) -> None:
        """Append one event (and stream it to the JSONL sink if any)."""
        event.setdefault("ts", round(time.time(), 6))
        with self._lock:
            self.events.append(event)
            if self.jsonl_path is not None:
                if self._jsonl is None:
                    d = os.path.dirname(self.jsonl_path)
                    if d:
                        os.makedirs(d, exist_ok=True)
                    self._jsonl = open(self.jsonl_path, "a")
                self._jsonl.write(json.dumps(event, default=str,
                                             sort_keys=True) + "\n")
                self._jsonl.flush()

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def event(self, name: str, **attrs) -> None:
        ev = {"type": "event", "name": name}
        ev.update(attrs)
        self.emit(ev)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] += n

    def observe(self, name: str, value: float) -> None:
        """Record one sample of a value stream (latency, occupancy, ...)."""
        with self._lock:
            self._samples.setdefault(name, []).append(float(value))

    def record_accuracy(self, **fields) -> dict:
        """Emit one predicted-vs-achieved throughput sample.

        The sample lands in the event buffer (``type="accuracy"``) and — when
        this recorder has a ``history_path`` — is appended to the
        schema-versioned history file so accuracy accumulates across
        processes (the calibration substrate, ROADMAP item 3).
        """
        from repro.obs import history
        sample = history.make_sample(fields)
        ev = {"type": "accuracy"}
        ev.update(sample)
        self.emit(ev)
        if self.history_path is not None:
            history.append_sample(self.history_path, sample)
        return sample

    # -- views ---------------------------------------------------------------

    def spans(self, name: Optional[str] = None) -> List[dict]:
        with self._lock:
            return [e for e in self.events if e.get("type") == "span"
                    and (name is None or e.get("name") == name)]

    def accuracy_samples(self) -> List[dict]:
        with self._lock:
            return [e for e in self.events if e.get("type") == "accuracy"]

    def counter(self, name: str) -> int:
        with self._lock:
            return self.counters.get(name, 0)

    def samples(self, name: str) -> List[float]:
        with self._lock:
            return list(self._samples.get(name, ()))

    def sample_sum(self, name: str) -> float:
        with self._lock:
            return float(sum(self._samples.get(name, ())))

    def percentile(self, name: str, q: float) -> float:
        """Nearest-rank percentile of a value stream (0 when empty)."""
        vals = self.samples(name)
        return percentile(vals, q)

    def percentiles(self, name: str,
                    qs: Sequence[float] = (50, 95, 99)) -> Dict[str, float]:
        vals = self.samples(name)
        return {f"p{q:g}": percentile(vals, q) for q in qs}

    def close(self) -> None:
        """Flush counters as a final event and close the JSONL sink."""
        with self._lock:
            counters = dict(self.counters)
        if counters:
            self.emit({"type": "counter", "counters": counters})
        with self._lock:
            if self._jsonl is not None:
                self._jsonl.close()
                self._jsonl = None

    def __enter__(self) -> "Recorder":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile; 0.0 for an empty stream."""
    if not values:
        return 0.0
    vals = sorted(values)
    k = max(0, min(len(vals) - 1,
                   int(round(q / 100.0 * (len(vals) - 1)))))
    return vals[k]
