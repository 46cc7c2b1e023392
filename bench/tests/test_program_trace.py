"""A window recorded on one TPU v5e with the program's own spans and named
kernels: three calls of 64 steps of the 2D r4 star at 1024^2 (plan
par_time 9: seven full supersteps and a one-step remainder a call)."""

import os
import re
from types import SimpleNamespace

from jax.profiler import ProfileData

from bench import counts, spec, xplane

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_star2d_r4_long_1024_spans.xplane.pb")


def _program_spans(path):
    """The host events named ``repro.*``, as (name, start, end) in ns."""
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for e in line.events
                   if e.name.startswith("repro.")), key=lambda e: e[1])


def test_kernels_are_named_by_role():
    trace = xplane.load(RECORDED)
    lo, hi = trace.window()
    kernels = [o for o in trace.devices[0]
               if o.kind == "kernel" and lo <= o.start < hi]
    names = [re.sub(r"\.\d+$", "", o.name) for o in kernels]
    assert names.count("stencil_superstep_plain") == 21
    assert names.count("stencil_remainder_plain") == 3
    top = xplane.breakdown(trace, [0], (lo, hi))["device_ops"]
    assert top[0][0].startswith("pallas_kernel %stencil_superstep_plain.")
    assert any(k.startswith("pallas_kernel %stencil_remainder_plain.")
               for k, _ in top)


def test_each_call_is_a_run_span_holding_copy_and_launch():
    trace = xplane.load(RECORDED)
    lo, hi = trace.window()
    spans = [s for s in _program_spans(RECORDED) if lo <= s[1] < hi]
    runs = [s for s in spans if s[0] == "repro.run"]
    dispatches = [s for s in trace.spans if s.name == "dispatch"]
    assert len(runs) == len(dispatches) == 3
    for (_, a, b), d in zip(runs, dispatches):
        assert d.start <= a <= b <= d.end
        inner = [n for n, s, e in spans if a <= s and e <= b
                 and n != "repro.run"]
        assert inner == ["repro.copy", "repro.launch"]


def test_existing_reducers_read_the_new_trace():
    trace = xplane.load(RECORDED)
    window = trace.window()
    ctx = SimpleNamespace(trace=trace, window=window, chips=1, devices=[0],
                          peak=counts.peaks("TPU v5 lite"), spans={},
                          counts={"flops": 3 * 64 * 1024**2 * 33,
                                  "bytes": 3 * 2 * 4 * 1024**2})
    assert 0 < spec.reducer("device_idle_pct")(ctx) < 100
    assert 0 < spec.reducer("nonkernel_device_pct")(ctx) < 5
    roof = spec.reducer("kernel_roofline_pct")(ctx)
    assert roof["bound"] == "vpu" and 0 < roof["value"] < 100
