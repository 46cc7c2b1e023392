"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh) cell — brief-fixed hardware constants
(v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s ICI link):

    compute   = HLO_FLOPs_per_device / peak_FLOPs
    memory    = HLO_bytes_per_device / HBM_bw
    collective= collective_bytes_per_device / ICI_link_bw

``compiled.cost_analysis()`` reports per-device FLOPs/bytes (verified against
a hand-checked partitioned matmul).  Collective bytes are not in
cost_analysis, so we parse ``compiled.as_text()``: a def-map per computation
resolves operand shapes, and while-loop ``known_trip_count`` backend configs
let collective bytes inside scanned layers count once per iteration —
without this, per-layer collectives would be undercounted by ~#layers.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

from repro.analysis.hw import TpuChip, V5E

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
_WHILE_RE = re.compile(
    r"while\(.*?body=%([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_TOAPPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_OPERANDS_RE = re.compile(r"%([\w.\-]+)")
_OPCODE_RE = re.compile(r"(?:\{[^}]*\}\s*)?([a-z][a-z0-9\-]*)\(")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

# 1 flop per output element (elementwise + transcendental, matching
# HloCostAnalysis conventions closely enough for a roofline).
_EW_OPS = frozenset("""
add subtract multiply divide maximum minimum power and or xor not negate abs
exponential exponential-minus-one log log-plus-one tanh rsqrt sqrt cbrt sine
cosine tan atan2 logistic select clamp compare floor ceil round-nearest-afz
round-nearest-even sign remainder is-finite
""".split())

# ops that move bytes but do no arithmetic
_FREE_OPS = frozenset("""
parameter constant tuple get-tuple-element bitcast after-all copy-start
copy-done partition-id replica-id rng-get-and-update-state custom-call
""".split())

# consumers that preserve "sliced" accounting for a fusion parameter: a
# param feeding dynamic-slice whose slice then flows through these still
# only touches slice-sized bytes
_LIGHT_OPS = frozenset("""
bitcast copy convert transpose reshape broadcast multiply add subtract
negate
""".split())


def _shape_bytes(type_str: str) -> int:
    """Bytes of the FIRST shape in a type string (e.g. 'f32[16,64]{1,0}')."""
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _shape_dims(type_str: str) -> Tuple[int, ...]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return ()
    dims = m.group(2)
    if not dims:
        return ()
    return tuple(int(d) for d in dims.split(","))


_PARAM_TYPE_RE = re.compile(r"[\w.\-]+:\s*([a-z0-9]+\[[\d,]*\])")
_TYPE_RE = re.compile(r"[a-z0-9]+\[[\d,]*\]")
_ENTRY_RE = re.compile(r"ENTRY\s+%?[\w.\-]+\s*\((.*)\)\s*->\s*(.*?)\s*\{?\s*$")
_ALIAS_PAIR_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{([\d,\s]*)\}(?:,\s*([\w-]+))?\)")


@dataclasses.dataclass(frozen=True)
class AliasPair:
    """One ``input_output_alias`` entry from an HLO module header.

    ``output_index`` indexes into the entry's (possibly tuple) result,
    ``param_number`` is the aliased entry parameter, ``param_index`` its
    tuple sub-index (usually empty).  ``kind`` is XLA's may/must-alias.
    """

    output_index: Tuple[int, ...]
    param_number: int
    param_index: Tuple[int, ...]
    kind: str = "may-alias"


def _index_tuple(text: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in text.replace(" ", "").split(",") if x)


def parse_input_output_aliases(hlo_text: str) -> List[AliasPair]:
    """Donation pairs from the module header's ``input_output_alias={...}``.

    Returns ``[]`` for modules without donation (XLA:CPU never records
    any — buffer donation is unimplemented there, which is exactly why
    ``repro.lint`` audits dumped artifacts instead of trusting the run).
    """
    m = re.search(r"input_output_alias=\{", hlo_text)
    if not m:
        return []
    depth, i = 1, m.end()
    while i < len(hlo_text) and depth:
        if hlo_text[i] == "{":
            depth += 1
        elif hlo_text[i] == "}":
            depth -= 1
        i += 1
    block = hlo_text[m.end():i - 1]
    return [AliasPair(_index_tuple(pm.group(1)), int(pm.group(2)),
                      _index_tuple(pm.group(3)), pm.group(4) or "may-alias")
            for pm in _ALIAS_PAIR_RE.finditer(block)]


def entry_signature(hlo_text: str) -> Tuple[List[str], List[str]]:
    """(param types, result types) of the ENTRY computation, layout-stripped.

    Each element is a bare ``dtype[dims]`` string (``"f32[4096,4096]"``).
    A tuple-typed result is flattened in index order, so ``results[i]`` is
    the type an ``AliasPair`` with ``output_index == (i,)`` refers to.
    """
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s.startswith("ENTRY"):
            continue
        em = _ENTRY_RE.match(s)
        if not em:
            continue
        params = _PARAM_TYPE_RE.findall(em.group(1))
        results = _TYPE_RE.findall(em.group(2))
        return params, results
    return [], []


def _result_bytes_all(rest: str) -> int:
    """Sum ALL shapes in the result type (handles tuple-typed whiles)."""
    opm = _OPCODE_RE.search(rest)
    head = rest[: opm.start()] if opm else rest
    total = 0
    for m in _SHAPE_RE.finditer(head):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _result_elems(rest: str) -> int:
    dims = _shape_dims(rest)
    n = 1
    for d in dims:
        n *= d
    return n


@dataclasses.dataclass
class _Collective:
    kind: str
    operand_bytes: int
    operand_names: List[str] = dataclasses.field(default_factory=list)
    wire_bytes: Optional[float] = None   # filled in second pass


@dataclasses.dataclass
class _Computation:
    name: str
    defs: Dict[str, Tuple[int, Tuple[int, ...]]]  # name -> (bytes, dims)
    collectives: List[_Collective]
    own_flops: float = 0.0
    own_bytes: float = 0.0
    # (kind, callee, trip): kind in {"fusion", "while", "cond"}
    calls: List[Tuple[str, str, int]] = dataclasses.field(
        default_factory=list)
    params: List[str] = dataclasses.field(default_factory=list)
    # param name -> bytes actually touched when the param is consumed only
    # by gather/dynamic-slice (result sizes), else absent -> full size
    sliced_params: Dict[str, float] = dataclasses.field(default_factory=dict)
    # params consumed by ops other than gather/dynamic-slice
    dense_params: set = dataclasses.field(default_factory=set)
    # bytes of dynamic-update-slice updates whose destination is a param
    # (in-place scan-grad accumulation: TPU aliases, traffic ~ update size)
    dus_update_bytes: float = 0.0
    dus_dest_params: set = dataclasses.field(default_factory=set)
    # value name -> originating param through light op chains
    alias: Dict[str, str] = dataclasses.field(default_factory=dict)
    # name -> producing (opcode, callee) for collective-operand resolution
    producers: Dict[str, Tuple[str, Optional[str]]] = \
        dataclasses.field(default_factory=dict)
    # True if this computation only converts/moves bytes (no arithmetic):
    # an f32 convert wrapper around a bf16 value (XLA excess-precision
    # folding) — its true wire width is its input width
    convert_only: bool = True
    param_bytes_total: float = 0.0
    # pending fusion byte estimate (filled in second pass)
    fusion_calls_bytes: List[Tuple[str, List[str], float]] = \
        dataclasses.field(default_factory=list)


def _parse_module(hlo_text: str):
    """Parse computations with per-instruction flop/byte/collective costs.

    FLOPs: dot = 2*M*N*K (batch dims included via result elems); elementwise
    and transcendental = 1/elem; reduce = input elems.  Bytes: per top-level
    instruction, operands + results (fusion internals excluded — the fusion
    boundary approximates HBM traffic on TPU).  Collectives: operand bytes.
    """
    comps: Dict[str, _Computation] = {}
    entry: Optional[str] = None
    cur: Optional[_Computation] = None

    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        header = _COMP_RE.match(line)
        if header and line.endswith("{"):
            cur = _Computation(header.group(1), {}, [])
            comps[cur.name] = cur
            if line.startswith("ENTRY"):
                entry = cur.name
            for pm in re.finditer(r"([\w.\-]+):\s*([a-z0-9]+\[[\d,]*\])",
                                  header.group(2)):
                cur.defs[pm.group(1)] = (_shape_bytes(pm.group(2)),
                                         _shape_dims(pm.group(2)))
                cur.params.append(pm.group(1))
                cur.param_bytes_total += _shape_bytes(pm.group(2))
            continue
        if cur is None:
            continue
        d = _DEF_RE.match(line)
        if not d:
            continue
        name, rest = d.group(1), d.group(2)
        cur.defs[name] = (_shape_bytes(rest), _shape_dims(rest))

        opm = _OPCODE_RE.search(rest)
        opcode = opm.group(1) if opm else ""
        args = rest[opm.end():].split(")", 1)[0] if opm else ""
        operand_names = [m.group(1) for m in _OPERANDS_RE.finditer(args)]
        operand_bytes = sum(cur.defs.get(n, (0, ()))[0]
                            for n in operand_names)
        cm0 = _CALLS_RE.search(rest)
        cur.producers[name] = (opcode, cm0.group(1) if cm0 else None)
        if opcode not in ("convert", "bitcast", "copy", "tuple",
                          "get-tuple-element", "parameter", "transpose",
                          "reshape"):
            cur.convert_only = False

        # track how computation parameters are consumed (gather-awareness).
        # Light shape/dtype ops (bitcast/transpose/convert…) propagate the
        # originating param, so "param -> bitcast -> dynamic-slice" still
        # counts slice-sized bytes.
        def _root(n):
            return cur.alias.get(n, n)

        if opcode in ("bitcast", "copy", "convert", "transpose", "reshape") \
                and operand_names:
            src = _root(operand_names[0])
            if src in cur.params:
                cur.alias[name] = src

        if opcode in ("gather", "dynamic-slice"):
            if operand_names and operand_names[0] in cur.defs:
                src = _root(operand_names[0])
                cur.sliced_params[src] = cur.sliced_params.get(src, 0.0) \
                    + _result_bytes_all(rest)
        elif opcode == "dynamic-update-slice":
            # in-place update of a carried buffer: touched ~ update bytes
            if len(operand_names) >= 2:
                upd = cur.defs.get(operand_names[1], (0, ()))[0]
                cur.dus_update_bytes += 2.0 * upd
                cur.dus_dest_params.add(_root(operand_names[0]))
        elif opcode not in _LIGHT_OPS and opcode not in _FREE_OPS:
            for n in operand_names:
                cur.dense_params.add(_root(n))

        # ---- call graph ----------------------------------------------------
        if opcode == "while":
            wm = _WHILE_RE.search(rest)
            if wm:
                trip = 1
                tm = _TRIP_RE.search(rest)
                if tm:
                    trip = int(tm.group(1))
                cur.calls.append(("while", wm.group(1), trip))
        elif opcode == "conditional":
            for cm in re.finditer(
                    r"(?:branch_computations=\{|true_computation=|"
                    r"false_computation=)%?([\w.\-]+)", rest):
                cur.calls.append(("cond", cm.group(1), 1))
        elif opcode == "call":
            # plain computation call (e.g. the CPU backend's parallel-task
            # wrappers): flops recurse like a fusion; bytes resolved below
            # with the callee's slice-awareness.
            am = _TOAPPLY_RE.search(rest)
            if am:
                cur.calls.append(("fusion", am.group(1), 1))
        else:
            for cm in _CALLS_RE.finditer(rest):
                cur.calls.append(("fusion", cm.group(1), 1))

        # ---- collectives ---------------------------------------------------
        # Wire bytes: an operand produced by a pure-convert fusion (XLA's
        # excess-precision f32 wrapper around bf16 values — a CPU-backend
        # pattern; TPU reduces natively in bf16) counts at its INPUT width.
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base in _COLLECTIVES and not opcode.endswith("-done"):
            cur.collectives.append(
                _Collective(base, operand_bytes, list(operand_names)))

        # ---- flops ---------------------------------------------------------
        if opcode == "dot":
            k = 1
            cm = _CDIMS_RE.search(rest)
            if cm and operand_names:
                lhs_dims = cur.defs.get(operand_names[0], (0, ()))[1]
                for ci in cm.group(1).split(","):
                    if ci and int(ci) < len(lhs_dims):
                        k *= lhs_dims[int(ci)]
            cur.own_flops += 2.0 * _result_elems(rest) * k
        elif opcode in _EW_OPS:
            cur.own_flops += _result_elems(rest)
        elif opcode == "reduce":
            if operand_names:
                dims = cur.defs.get(operand_names[0], (0, ()))[1]
                n = 1
                for dd in dims:
                    n *= dd
                cur.own_flops += n

        # ---- bytes (top-level only; fusion internals estimated later) ------
        if opcode in _FREE_OPS or opcode in ("while", "conditional"):
            pass
        elif opcode in ("fusion", "call"):
            # resolved in a second pass once the callee is parsed
            callee = None
            cm = _CALLS_RE.search(rest) if opcode == "fusion" \
                else _TOAPPLY_RE.search(rest)
            if cm:
                callee = cm.group(1)
            cur.fusion_calls_bytes.append(
                (callee, operand_names, _result_bytes_all(rest)))
        elif opcode in ("gather", "dynamic-slice"):
            # touched bytes ~ result (+ indices), not the whole source
            idx_bytes = sum(cur.defs.get(n, (0, ()))[0]
                            for n in operand_names[1:])
            cur.own_bytes += 2.0 * _result_bytes_all(rest) + idx_bytes
        elif opcode in ("scatter", "dynamic-update-slice"):
            # in-place update: traffic ~ updates (read+write) + indices
            upd = cur.defs.get(operand_names[-1], (0, ()))[0] \
                if operand_names else 0
            idx = sum(cur.defs.get(n, (0, ()))[0]
                      for n in operand_names[1:-1])
            cur.own_bytes += 2.0 * upd + idx
        else:
            cur.own_bytes += operand_bytes + _result_bytes_all(rest)

    # second pass: resolve collective wire widths through convert wrappers
    for comp in comps.values():
        for c in comp.collectives:
            wire = 0.0
            for n in c.operand_names:
                full = comp.defs.get(n, (0, ()))[0]
                op, callee = comp.producers.get(n, ("", None))
                if op == "fusion" and callee in comps \
                        and comps[callee].convert_only:
                    wire += min(float(full),
                                comps[callee].param_bytes_total)
                elif op == "convert":
                    wire += full   # single convert: width genuinely changes
                else:
                    wire += full
            c.wire_bytes = wire

    # third pass: fusion byte estimates with gather/DUS-aware operand costs
    def _unwrap(sub, depth=0):
        """Follow trivial wrapper computations (a single fusion/call whose
        operands are exactly the wrapper's params, e.g. the CPU backend's
        ``parallel_*`` outer-partitioned wrappers) to the computation that
        actually consumes the params, so slice-awareness survives the hop."""
        while sub is not None and depth < 8:
            if (len(sub.fusion_calls_bytes) == 1
                    and sub.fusion_calls_bytes[0][0]
                    and list(sub.fusion_calls_bytes[0][1]) == list(sub.params)):
                nxt = comps.get(sub.fusion_calls_bytes[0][0])
                if nxt is None:
                    break
                sub = nxt
                depth += 1
            else:
                break
        return sub

    for comp in comps.values():
        for callee, operand_names, result_bytes in comp.fusion_calls_bytes:
            sub = _unwrap(comps.get(callee)) if callee else None
            total = result_bytes
            if sub is not None and sub.dus_dest_params:
                # fusion wraps an in-place dynamic-update-slice: the full-
                # buffer result aliases its destination operand on TPU —
                # count update traffic, not the whole buffer.
                total = sub.dus_update_bytes
            for i, oname in enumerate(operand_names):
                full = comp.defs.get(oname, (0, ()))[0]
                if (sub is not None and i < len(sub.params)):
                    pname = sub.params[i]
                    if pname in sub.dus_dest_params:
                        continue   # destination buffer aliases; counted above
                    if (pname in sub.sliced_params
                            and pname not in sub.dense_params):
                        total += min(float(full), sub.sliced_params[pname])
                        continue
                total += full
            comp.own_bytes += total

    return comps, entry


def parse_hlo_costs(hlo_text: str) -> Dict[str, float]:
    """Recursive per-device cost accounting with while trip counts applied.

    XLA's ``compiled.cost_analysis()`` counts while bodies ONCE (verified:
    a 10-step scanned matmul reports 1/10th the unrolled flops), which would
    undercount scanned-layer models by ~n_layers.  This walker multiplies
    through ``known_trip_count`` instead.
    """
    comps, entry = _parse_module(hlo_text)

    memo_f: Dict[str, Tuple[float, float]] = {}
    memo_c: Dict[str, Dict[str, float]] = {}

    def walk_fb(name: str, depth: int = 0) -> Tuple[float, float]:
        """(flops, bytes): flops recurse into fusions; bytes do not."""
        if name in memo_f:
            return memo_f[name]
        comp = comps.get(name)
        if comp is None or depth > 64:
            return (0.0, 0.0)
        memo_f[name] = (0.0, 0.0)
        fl, by = comp.own_flops, comp.own_bytes
        for kind, callee, trip in comp.calls:
            cf, cb = walk_fb(callee, depth + 1)
            if kind == "fusion":
                fl += cf            # fused elementwise arithmetic
            else:
                fl += trip * cf
                by += trip * cb
        memo_f[name] = (fl, by)
        return memo_f[name]

    def walk_c(name: str, depth: int = 0) -> Dict[str, float]:
        if name in memo_c:
            return memo_c[name]
        comp = comps.get(name)
        acc = {k: 0.0 for k in _COLLECTIVES}
        if comp is None or depth > 64:
            return acc
        memo_c[name] = acc
        for c in comp.collectives:
            acc[c.kind] += (c.wire_bytes if c.wire_bytes is not None
                            else c.operand_bytes)
        for kind, callee, trip in comp.calls:
            sub = walk_c(callee, depth + 1)
            for k in acc:
                acc[k] += trip * sub[k]
        return acc

    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    flops = byts = 0.0
    if entry is not None:
        flops, byts = walk_fb(entry)
        out = walk_c(entry)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["flops"] = flops
    out["bytes"] = byts
    return out


def parse_collectives(hlo_text: str) -> Dict[str, float]:
    """Back-compat wrapper returning only the collective byte counts."""
    c = parse_hlo_costs(hlo_text)
    return {k: v for k, v in c.items() if k not in ("flops", "bytes")}


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as one dict (a list of per-device
    dicts keeps its first entry)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca)


@dataclasses.dataclass
class RooflineCell:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_breakdown: Dict[str, float]
    peak_memory_per_device: int
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    model_flops: float
    useful_ratio: float
    notes: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
            model_flops: float, hw: TpuChip = V5E,
            notes: str = "") -> RooflineCell:
    coll = parse_hlo_costs(compiled.as_text())
    flops = float(coll["flops"])
    byts = float(coll["bytes"])
    ma = compiled.memory_analysis()
    peak = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes)

    t_c = flops / hw.peak_bf16_flops
    t_m = byts / hw.hbm_bytes_per_s
    t_x = coll["total"] / hw.ici_link_bytes_per_s
    dominant = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
                   key=lambda kv: kv[1])[0]
    total_flops = flops * chips
    return RooflineCell(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=byts,
        coll_bytes_per_device=coll["total"],
        coll_breakdown={k: v for k, v in coll.items()
                        if k in _COLLECTIVES},
        peak_memory_per_device=peak,
        t_compute=t_c, t_memory=t_m, t_collective=t_x,
        dominant=dominant,
        model_flops=model_flops,
        useful_ratio=(model_flops / total_flops) if total_flops else 0.0,
        notes=notes,
    )


def save_cell(cell: RooflineCell, path: str):
    with open(path, "w") as f:
        json.dump(cell.to_json(), f, indent=1)
