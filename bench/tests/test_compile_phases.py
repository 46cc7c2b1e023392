"""The ``setup.*_s`` compile-phase reducers: they read the program's
``repro.obs.compile_totals()`` where a chip was traced, and nothing on a
host without one or from a program without the counters."""

import os
from types import SimpleNamespace

import pytest

from bench import spec
from bench.tests.synthetic import KERNEL, hlo, write

PHASES = {"setup.trace_s": ("/jax/core/compile/jaxpr_trace_duration", 1.5),
          "setup.lower_s": ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                            2.25),
          "setup.compile_s": ("/jax/core/compile/backend_compile_duration",
                              0.5)}


@pytest.fixture
def obs():
    from repro import obs
    from repro.obs import profiler
    obs.reset()
    t = 0.0
    with obs.span("run"):                  # as inside the warm-up call
        for event, seconds in PHASES.values():
            profiler._on_phase(event, t, t + seconds)
            t += seconds
    yield obs
    obs.reset()


def _ctx(tmp_path, devices):
    from bench import xplane
    path = write(os.path.join(tmp_path, "t.xplane.pb"), devices,
                 [("window", 0, 100000)])
    trace = xplane.load(path)
    return SimpleNamespace(trace=trace, window=trace.window(), chips=1,
                           devices=sorted(trace.devices)[:1], peak=None,
                           spans={"plan": 1.0, "first_call": 9.0},
                           counts={"flops": 0.0, "bytes": 0.0})


@pytest.mark.parametrize("name", sorted(PHASES))
def test_phase_read_where_a_chip_was_traced(obs, tmp_path, name):
    ctx = _ctx(str(tmp_path), {0: [(hlo("k.1", "custom-call", KERNEL),
                                    0, 50000)]})
    assert spec.reducer(name)(ctx) == pytest.approx(PHASES[name][1])
    assert sum(spec.reducer(n)(ctx) for n in PHASES) <= \
        ctx.spans["first_call"]


@pytest.mark.parametrize("name", sorted(PHASES))
def test_phase_not_read_without_a_chip_or_the_counters(obs, tmp_path,
                                                       monkeypatch, name):
    # a CPU run: the trace holds no TPU plane
    assert spec.reducer(name)(_ctx(str(tmp_path), {})) is None
    # a program whose obs has no compile totals
    ctx = _ctx(str(tmp_path), {0: [(hlo("k.1", "custom-call", KERNEL),
                                    0, 50000)]})
    monkeypatch.delattr(obs, "compile_totals")
    assert spec.reducer(name)(ctx) is None
