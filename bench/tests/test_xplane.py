"""The trace reduction: device intervals, kernels, idle time, exposed
collectives and the breakdown, on small synthetic ``.xplane.pb`` files."""

import os
from types import SimpleNamespace

import pytest

from bench import counts, spec, xplane
from bench.tests.synthetic import KERNEL, hlo, write

PEAK = counts.peaks("TPU v5 lite")

# One chip, window [0, 100 us].  A while loop [10, 90] holds two kernels;
# copy, pad and slice sit outside it; 12 us are idle.
ONE_CHIP = {0: [
    (hlo("copy.1", "copy"), 0, 4000),
    (hlo("pad.0", "pad"), 5000, 4000),
    ("%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %tuple.1), "
     "condition=%c, body=%b", 10000, 80000),
    (hlo("body.6", "custom-call", KERNEL), 10000, 30000),
    (hlo("body.7", "custom-call", KERNEL), 45000, 30000),
    (hlo("slice.1", "slice"), 80000, 8000),
]}
SPANS = [("window", 0, 100000), ("call", 0, 100000),
         ("dispatch", 0, 2000), ("block", 2000, 98000)]


def _ctx(tmp_path, devices, spans, chips=1, flops=0.0, nbytes=0.0,
         host=None):
    path = write(os.path.join(tmp_path, "t.xplane.pb"), devices, spans)
    trace = xplane.load(path)
    return SimpleNamespace(trace=trace, window=trace.window(), chips=chips,
                           devices=sorted(trace.devices)[:chips],
                           peak=PEAK, spans=host or {},
                           counts={"flops": flops, "bytes": nbytes})


def test_parse_hlo_names_and_opcodes():
    assert xplane.parse_hlo(hlo("pad.0", "pad")) == ("pad.0", "pad")
    assert xplane.parse_hlo(
        "%while.1 = (s32[]{:T(128)}, f32[2]) while((s32[], f32[2]) %t)"
    ) == ("while.1", "while")
    name, op = xplane.parse_hlo(hlo("body.7", "custom-call", KERNEL))
    assert (name, op) == ("body.7", "custom-call")
    assert xplane.classify(hlo("b", "custom-call", KERNEL), op) == "kernel"
    assert xplane.classify("", "collective-permute-done") == "collective"
    assert xplane.parse_hlo("jit_run_call(123)") == ("jit_run_call(123)",) * 2


def test_leaves_window_and_spans(tmp_path):
    ctx = _ctx(str(tmp_path), ONE_CHIP, SPANS)
    ops = ctx.trace.devices[0]
    assert [o.name for o in ops if not o.leaf] == ["while.1"]
    assert sum(o.kind == "kernel" for o in ops) == 2
    assert ctx.window == (0, 100000)
    assert [s.name for s in ctx.trace.spans][:2] == ["window", "call"]


def test_idle_and_nonkernel(tmp_path):
    ctx = _ctx(str(tmp_path), ONE_CHIP, SPANS)
    idle = spec.reducer("device_idle_pct")(ctx)
    assert idle == pytest.approx(12.0)
    # copy + pad + slice = 16 us of 88 us busy
    assert spec.reducer("nonkernel_device_pct")(ctx) == pytest.approx(
        100 * 16 / 88)


def test_kernel_roofline_names_its_bound(tmp_path):
    # 60 us of kernel time; a VPU bound of 6 us, an HBM bound of 1.2 us
    flops = 6e-6 * PEAK["peak_vpu_f32_flops"]
    nbytes = 1.2e-6 * PEAK["hbm_bytes_per_s"]
    ctx = _ctx(str(tmp_path), ONE_CHIP, SPANS, flops=flops, nbytes=nbytes)
    got = spec.reducer("kernel_roofline_pct")(ctx)
    assert got["bound"] == "vpu"
    assert got["value"] == pytest.approx(10.0)
    ctx.counts = {"flops": flops / 10, "bytes": nbytes * 10}
    got = spec.reducer("kernel_roofline_pct")(ctx)
    assert got["bound"] == "hbm"
    assert got["value"] == pytest.approx(20.0)


def test_exchange_exposed_takes_the_worst_chip(tmp_path):
    devices = {
        0: [(hlo("collective-permute-done.1", "collective-permute-done"),
             20000, 10000),
            (hlo("body.1", "custom-call", KERNEL), 25000, 25000)],
        1: [(hlo("collective-permute-done.1", "collective-permute-done"),
             20000, 10000),
            (hlo("body.1", "custom-call", KERNEL), 28000, 25000)],
    }
    ctx = _ctx(str(tmp_path), devices, SPANS, chips=2)
    assert ctx.devices == [0, 1]
    # chip 0 hides all but 5 us, chip 1 all but 8 us
    assert spec.reducer("exchange_exposed_pct")(ctx) == pytest.approx(8.0)


def test_nothing_to_read_gives_nothing(tmp_path):
    ctx = _ctx(str(tmp_path), ONE_CHIP, SPANS)
    assert spec.reducer("exchange_exposed_pct")(ctx) is None
    ctx.trace, ctx.devices = None, []
    for name in ("kernel_roofline_pct", "nonkernel_device_pct",
                 "device_idle_pct", "exchange_exposed_pct"):
        assert spec.reducer(name)(ctx) is None


def test_setup_spans_come_from_the_host_clock(tmp_path):
    ctx = _ctx(str(tmp_path), ONE_CHIP, SPANS,
               host={"plan": 1.5, "first_call": 7.25})
    assert spec.reducer("setup.plan_s")(ctx) == 1.5
    assert spec.reducer("setup.first_call_s")(ctx) == 7.25


def test_breakdown_ops_and_labelled_gaps(tmp_path):
    ctx = _ctx(str(tmp_path), ONE_CHIP, SPANS)
    b = xplane.breakdown(ctx.trace, ctx.devices, ctx.window)
    assert b["device_ops"][0] == ["pallas_kernel %body.6", 30e-6]
    assert ["slice %slice.1", 8e-6] in b["device_ops"]
    assert all(len(e) == 2 for e in b["device_ops"] + b["idle_gaps"])
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # gaps: [90, 100] (while blocked), [9, 10] and [4, 5] (dispatch
    # ends at 2 us, so the host is blocked there too)
    assert b["idle_gaps"][0] == ["block", 10e-6]
    assert [g[1] for g in b["idle_gaps"]] == [10e-6, 1e-6, 1e-6]


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xplane.length([(0, 2), (1, 3), (10, 11)]) == 4
    assert xplane.minus([(0, 10)], [(2, 3), (5, 20)]) == 4
    assert xplane.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]
    assert xplane.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_star2d_r4_long_1024.xplane.pb")


def test_recorded_v5e_trace():
    """A window recorded on one TPU v5e: three calls of 64 steps of the 2D
    r4 star at 1024^2 (plan par_time 9: seven full supersteps and a
    remainder a call), with the harness's spans."""
    trace = xplane.load(RECORDED)
    window = trace.window()
    assert list(trace.devices) == [0] and window is not None
    assert {s.name for s in trace.spans} == {"window", "call", "dispatch",
                                             "block"}
    kernels = [o for o in trace.devices[0] if o.kind == "kernel"
               and window[0] <= o.start < window[1]]
    assert len(kernels) == 24 and all(o.leaf for o in kernels)
    assert {o.opcode for o in trace.devices[0] if not o.leaf} == {
        "while", "conditional"}
    ctx = SimpleNamespace(trace=trace, window=window, chips=1, devices=[0],
                          peak=PEAK, spans={},
                          counts={"flops": 3 * 64 * 1024**2 * 33,
                                  "bytes": 3 * 2 * 4 * 1024**2})
    idle = spec.reducer("device_idle_pct")(ctx)
    nonkernel = spec.reducer("nonkernel_device_pct")(ctx)
    roof = spec.reducer("kernel_roofline_pct")(ctx)
    assert 0 < idle < 100 and 0 < nonkernel < 5
    assert roof["bound"] == "vpu" and 0 < roof["value"] < 100
    b = xplane.breakdown(trace, [0], window)
    assert b["device_ops"][0][0].startswith("pallas_kernel")
    assert b["idle_gaps"] and len(b["device_ops"]) == 10


@pytest.mark.parametrize("name", sorted(
    f[:-3] for f in os.listdir(os.path.join(spec.ROOT, "bench", "metrics"))
    if f.endswith(".py")))
def test_reducer_with_nothing_to_read_returns_nothing(name):
    """No trace (a CPU run, or ``--trace 0``) and no set-up spans: every
    reducer returns None, so the harness leaves its metric out."""
    ctx = SimpleNamespace(trace=None, window=None, chips=1, devices=[],
                          peak=PEAK, spans={},
                          counts={"flops": 0.0, "bytes": 0.0})
    assert spec.reducer(name)(ctx) is None
