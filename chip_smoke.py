#!/usr/bin/env python3
"""Drive the fused stencil executor once on a TPU and check every answer.

    python chip_smoke.py           # one chip: the paper's grids + serving
    python chip_smoke.py --mesh    # four chips: the 2x2-mesh path only

One process runs every phase through the user's entry points
(``repro.stencil(program).compile(...).run(grid)`` and ``StencilServer``)
with the compiled ``pallas-tpu`` backend, so a missing chip is an error and
nothing falls back to the interpreter.  Each result is checked against

* the ``xla-reference`` lowering compiled on the same chip (the full grid,
  ``|pallas - xla| <= XLA_ATOL``), and
* the float64 numpy oracle on corner windows (``|pallas - oracle| <=
  ORACLE_ATOL``): a cell more than ``steps * radius`` from a window's cut
  edges depends only on cells inside the window.

Wall times are printed as information, not as benchmark numbers.  The last
line of standard output is ``{"ok": true, "device": {...}}`` on success; any
failed phase exits 1 and a host without a TPU exits 2, neither printing it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Pallas vs the xla-reference lowering, both float32 on the chip.
XLA_ATOL = 1e-5
#: Pallas (float32) vs the float64 numpy oracle.
ORACLE_ATOL = 5e-4


def info(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def max_abs_diff(a, b) -> float:
    """max |a - b| on b's device, in slabs of the leading axis: a 3D paper
    grid leaves no HBM for a whole-grid temporary beside a, b and the
    input."""
    import jax
    import jax.numpy as jnp
    a = jax.device_put(a, list(b.devices())[0])
    step = max(1, a.shape[0] // 8)
    return max(float(jnp.max(jnp.abs(a[i:i + step] - b[i:i + step])))
               for i in range(0, a.shape[0], step))


def check_full(name, out, want, against="xla-reference") -> None:
    """Whole-grid agreement with an independent result."""
    err = max_abs_diff(out, want)
    info(f"{name}: max|pallas - {against}| = {err:.3e} "
         f"(tolerance {XLA_ATOL})")
    if not err <= XLA_ATOL:
        raise AssertionError(f"{name}: differs from {against} by {err}")


def oracle_windows(program, grid_shape, steps, side):
    """(window, checked) slice pairs for the float64 oracle.

    Under clamp/constant the windows sit on the low and the high corner of
    the grid, so the true borders are inside them; under periodic one
    window straddles the wrap (indices taken modulo the extent).  Cells of
    ``checked`` are more than ``steps * radius`` from every cut edge.
    """
    import numpy as np
    m = steps * program.halo_radius
    if program.boundary == "periodic":
        idx = [np.arange(-m, side + m) % n for n in grid_shape]
        return [(idx, tuple(slice(m, m + side) for _ in grid_shape))]
    out = []
    for corner in ("lo", "hi"):
        idx, chk = [], []
        for n in grid_shape:
            w = min(n, side + m)
            if corner == "lo":
                idx.append(np.arange(0, w))
                chk.append(slice(0, w - m if w < n else w))
            else:
                idx.append(np.arange(n - w, n))
                chk.append(slice(m if w < n else 0, w))
        out.append((idx, tuple(chk)))
    return out


def check_oracle(name, program, coeffs, grid, out, steps, side) -> None:
    """Corner windows agree with the float64 numpy oracle."""
    import numpy as np
    from repro.core.reference import numpy_program_nsteps
    for idx, chk in oracle_windows(program, grid.shape, steps, side):
        ix = np.ix_(*idx)       # gathered on the device: only windows move
        want = numpy_program_nsteps(program, coeffs, np.asarray(grid[ix]),
                                    steps)[chk]
        err = float(np.max(np.abs(np.asarray(out[ix])[chk] - want)))
        info(f"{name}: max|pallas - float64 oracle| = {err:.3e} over "
             f"{want.shape} window (tolerance {ORACLE_ATOL})")
        if not err <= ORACLE_ATOL:
            raise AssertionError(f"{name}: differs from oracle by {err}")


def timed_run(name, cs, grid, steps):
    """First call (trace + compile + run), then a timed second run."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(cs.run(grid, steps))
    t1 = time.perf_counter()
    del out
    out = jax.block_until_ready(cs.run(grid, steps))
    t2 = time.perf_counter()
    info(f"{name}: first call {t1 - t0:.3f} s (compile + run), "
         f"second run {t2 - t1:.3f} s (information only)")
    return out


def assert_compiled(name, cs) -> None:
    if not (cs.backend.startswith("pallas-tpu") and cs.interpret is False):
        raise AssertionError(
            f"{name}: ran on {cs.backend} interpret={cs.interpret}, not the "
            f"compiled pallas-tpu family")


def reference(program, shape, steps, grid):
    import repro
    ref = repro.stencil(program).compile(shape, steps=steps, plan="model",
                                         backend="xla-reference")
    return ref.run(grid, steps)


def run_case(name, program, shape, variant, *, side, seed=0,
             devices=None, compare=None):
    """One front-door run at ``shape``: compile, run, check; returns out.

    ``compare`` replaces the xla-reference check by agreement with that
    (one-chip) result.
    """
    import jax
    import repro
    from repro.core.blocking import TEMPORAL_CHUNK
    from repro.core.reference import random_grid

    kw = dict(plan="auto", cache=False, backend="pallas-tpu",
              variant=variant, devices=devices)
    pt = repro.stencil(program).compile(shape, steps=1, **kw).plan.par_time
    # full supersteps and a remainder: two plain ones (one trip of the
    # executor's two-superstep loop), one temporal chunk (its odd tail)
    steps = (TEMPORAL_CHUNK if variant == "temporal" else 2) * pt + 1
    t0 = time.perf_counter()
    cs = repro.stencil(program).compile(shape, steps=steps, **kw)
    info(f"{name}: {cs!r} steps={steps}, plan resolved in "
         f"{time.perf_counter() - t0:.3f} s")
    assert_compiled(name, cs)
    grid = random_grid(program, shape, seed=seed)
    out = timed_run(name, cs, grid, steps)
    if devices is not None:
        spans = len(out.sharding.device_set)
        info(f"{name}: result spans {spans} devices")
        if spans != devices:
            raise AssertionError(f"{name}: result spans {spans} devices, "
                                 f"not {devices}")
    if compare is None:
        want = jax.block_until_ready(reference(program, shape, steps, grid))
        check_full(name, out, want)
        del want
    else:
        check_full(name, out, compare(grid, steps), "one-chip run")
    check_oracle(name, program, cs.coeffs, grid, out, steps, side)
    return out


def served(name):
    """StencilServer answers 8 requests of 2048^2 (r=2) in batches of 4."""
    import numpy as np
    import jax
    from repro.core.program import StencilProgram
    from repro.launch.stencil_serve import StencilServer

    program = StencilProgram(ndim=2, radius=2)
    shape, steps = (2048, 2048), 7
    server = StencilServer(max_batch=4)
    rng = np.random.RandomState(0)
    grids = [rng.uniform(-1, 1, shape).astype(np.float32) for _ in range(8)]
    rids = [server.submit(program, g, steps) for g in grids]
    t0 = time.perf_counter()
    results = server.flush()
    info(f"{name}: 8 requests flushed in {time.perf_counter() - t0:.3f} s "
         f"(includes compile; information only)")
    if server.failed:
        raise AssertionError(f"{name}: failed requests {server.failed}")
    missing = [r for r in rids if r not in results]
    if missing:
        raise AssertionError(f"{name}: no result for rids {missing}")
    for cs in server._compiled.values():
        assert_compiled(name, cs)
    batched = [cs for cs in server._compiled.values() if cs.batch]
    if not batched:
        raise AssertionError(f"{name}: no batched executable ran")
    coeffs = program.default_coeffs()
    for rid, g in zip(rids, grids):
        want = reference(program, shape, steps, jax.numpy.asarray(g))
        check_full(f"{name} rid={rid}", jax.numpy.asarray(results[rid]),
                   want)
    check_oracle(f"{name} rid={rids[0]}", program, coeffs, grids[0],
                 results[rids[0]], steps, side=256)


def one_chip_phases():
    from repro.configs import stencil2d, stencil3d
    p2 = stencil2d.workloads()["2d_r4_paper"]
    p3 = stencil3d.workloads()["3d_r4_paper"]
    box = stencil2d.workloads()["2d_box_periodic_pod"].spec
    phases = []
    for v in ("plain", "pipelined", "temporal"):
        phases.append((f"2d_r4_paper {v}", lambda v=v: run_case(
            f"2d_r4_paper {v}", p2.spec, p2.grid_shape, v, side=256)))
    for v in ("plain", "pipelined", "temporal"):
        phases.append((f"3d_r4_paper {v}", lambda v=v: run_case(
            f"3d_r4_paper {v}", p3.spec, p3.grid_shape, v, side=32)))
    phases.append(("2d_box_periodic 16384^2", lambda: run_case(
        "2d_box_periodic 16384^2", box, (16384, 16384), "plain",
        side=256)))
    phases.append(("served", lambda: served("served")))
    return phases


def mesh_phases():
    """The 2x2-mesh path against the one-chip run of the same grid."""
    import jax
    import repro
    from repro.configs import stencil2d, stencil3d

    def one_chip(program, shape):
        def run(grid, steps):
            cs = repro.stencil(program).compile(
                shape, steps=steps, plan="auto", cache=False,
                backend="pallas-tpu", variant="plain")
            assert_compiled("one-chip", cs)
            return jax.block_until_ready(cs.run(grid, steps))
        return run

    p2 = stencil2d.workloads()["2d_r4_pod"].spec
    p3 = stencil3d.workloads()["3d_r4_pod"].spec
    cases = [("2d r4 8192^2 on 2x2", p2, (8192, 8192), 256),
             ("3d r4 128x512x2048 on 2x2", p3, (128, 512, 2048), 32)]
    return [(name, lambda name=name, p=p, s=s, side=side: run_case(
        name, p, s, "plain", side=side, devices=4,
        compare=one_chip(p, s))) for name, p, s, side in cases]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the 2x2-mesh path (needs four chips)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX runs on {dev.platform}",
              file=sys.stderr)
        return 2
    info(f"device kind={dev.device_kind!r} count={len(devices)}")
    if args.mesh and len(devices) < 4:
        print(f"[chip_smoke] --mesh needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    info(f"compile cache: {enable_compile_cache()}")

    failed = []
    for name, phase in (mesh_phases() if args.mesh else one_chip_phases()):
        t0 = time.perf_counter()
        try:
            phase()
            mem = dev.memory_stats() or {}
            info(f"PASS {name} ({time.perf_counter() - t0:.1f} s, peak HBM "
                 f"in use {mem.get('peak_bytes_in_use', 0) / 2**30:.2f} "
                 f"GiB)")
        except Exception:
            traceback.print_exc()
            info(f"FAIL {name}")
            failed.append(name)
    if failed:
        print(f"[chip_smoke] failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
