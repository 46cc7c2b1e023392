"""Read a JAX profiler trace of the window into intervals, and reduce it.

``load(path)`` turns an ``.xplane.pb`` into a :class:`Trace`: for every TPU
device plane the operations of its ``XLA Ops`` line, and the harness's own
host spans (``jax.profiler.TraceAnnotation`` names that start with
``bench.``), all on the profiler's one clock in nanoseconds.

An operation's event name is its HLO text (``%name = shape opcode(...)``).
A Pallas kernel is a ``custom-call`` whose target is ``tpu_custom_call``; a
collective is one of :data:`COLLECTIVES`.  Control-flow ops (``while``,
``conditional``) span the ops they run, so only *leaf* ops (those that
contain no other op on the line) count as work; the union of all ops is the
device's busy time.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter", "collective-broadcast",
               "send", "recv")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Op:
    name: str            # HLO instruction name, without the leading %
    opcode: str
    start: float         # ns
    end: float           # ns
    kind: str = "other"  # "kernel" | "collective" | "other"
    leaf: bool = True


@dataclasses.dataclass
class Span:
    name: str            # without the "bench." prefix
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Op]]
    spans: List[Span]

    def window(self) -> Optional[Tuple[float, float]]:
        w = [s for s in self.spans if s.name == "window"]
        return (w[0].start, w[0].end) if w else None


def parse_hlo(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an HLO op's text; for a name that is
    not HLO text, (name, name)."""
    if not text.startswith("%") or " = " not in text:
        return text, text
    name, rest = text[1:].split(" = ", 1)
    if rest.startswith("("):             # a tuple shape: skip its parens
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else rest
    return name, rest.split("(", 1)[0]


def classify(text: str, opcode: str) -> str:
    if opcode == "custom-call" and "tpu_custom_call" in text:
        return "kernel"
    if opcode.startswith(COLLECTIVES):
        return "collective"
    return "other"


def _mark_leaves(ops: List[Op]) -> List[Op]:
    """An op that holds the next op (in start order) inside it runs other
    ops (``while``, ``conditional``) and is not a leaf."""
    ops.sort(key=lambda o: (o.start, -o.end))
    for a, b in zip(ops, ops[1:]):
        if b.start < a.end and b.end <= a.end:
            a.leaf = False
    return ops


def from_profile(pd) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    devices: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                ops = devices.setdefault(int(m.group(1)), [])
                for e in line.events:
                    name, opcode = parse_hlo(e.name)
                    ops.append(Op(name, opcode, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  classify(e.name, opcode)))
            elif not m:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name[len(SPAN_PREFIX):],
                                          e.start_ns,
                                          e.start_ns + e.duration_ns))
    for ops in devices.values():
        _mark_leaves(ops)
    spans.sort(key=lambda s: s.start)
    return Trace(devices, spans)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


# ---- interval arithmetic (ns) ------------------------------------------------

def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def minus(a, b) -> float:
    """Length of union(a) not covered by union(b)."""
    ua, ub = union(a), union(b)
    covered, j = 0.0, 0
    for lo, hi in ua:
        while j < len(ub) and ub[j][1] <= lo:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < hi:
            covered += min(hi, ub[k][1]) - max(lo, ub[k][0])
            k += 1
    return length(ua) - covered


def gaps(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for a, b in union(clip(intervals, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


# ---- what every reducer needs --------------------------------------------------

def device_ops(trace: Trace, device: int, window, kind=None, leaf=None):
    lo, hi = window
    return clip([(o.start, o.end) for o in trace.devices.get(device, ())
                 if (kind is None or o.kind == kind)
                 and (leaf is None or o.leaf == leaf)], lo, hi)


def busy_ns(trace: Trace, device: int, window) -> float:
    return length(device_ops(trace, device, window))


def breakdown(trace: Trace, devices, window, top: int = 10) -> dict:
    """The device ops that took most time (seconds a chip, averaged over the
    chips) and the longest idle gaps, each named by the harness span the host
    was in (``between_calls`` where it was in none)."""
    totals: Dict[str, float] = {}
    for d in devices:
        for o in trace.devices.get(d, ()):
            if not o.leaf:
                continue
            spans = clip([(o.start, o.end)], *window)
            if spans:
                label = ("pallas_kernel" if o.kind == "kernel"
                         else o.opcode) + f" %{o.name}"
                totals[label] = totals.get(label, 0.0) + length(spans)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    host = [s for s in trace.spans if s.name not in ("window", "call")]
    idle = []
    for d in devices:
        for a, b in gaps([(o.start, o.end)
                          for o in trace.devices.get(d, ())], *window):
            best, label = 0.0, "between_calls"
            for s in host:
                overlap = min(b, s.end) - max(a, s.start)
                if overlap > best:
                    best, label = overlap, s.name
            idle.append((b - a, label))
    idle.sort(reverse=True)
    n = max(1, len(devices))
    return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
            "idle_gaps": [[label, ns / 1e9] for ns, label in idle[:top]]}
