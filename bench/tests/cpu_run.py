"""Drive one benchmark run on the CPU at a small grid, past the look for a
chip, optionally with the timed path broken.  Used by the tests, always in
a subprocess (a mesh needs its CPU devices before JAX is imported).

    python bench/tests/cpu_run.py <cell> <grid, e.g. 64x256> <seed> \\
        <seconds> <trace 0|1> [fault] [devices]

With ``devices`` > 1 the cell runs on that many CPU devices, through the
front door's ``devices=`` and the sharded reference.

Faults: ``unchanged`` (every call returns its input), ``altered`` (one
cell of every call's answer is changed where it is produced),
``no_exchange`` (the halo exchange between chips is left out).
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
cell_name, grid, seed, seconds, traced = sys.argv[1:6]
fault = sys.argv[6] if len(sys.argv) > 6 else "none"
devices = int(sys.argv[7]) if len(sys.argv) > 7 else 1
os.environ["JAX_PLATFORMS"] = "cpu"
if devices > 1:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{devices}")
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, spec  # noqa: E402

cell = spec.resolve(cell_name, ROOT)
cell.config = dict(cell.config, grid=[int(n) for n in grid.split("x")])
if devices > 1:                 # the same cell, split over a 2x2 mesh
    cell.chips = devices
    cell.config["reference_mesh"] = [2, devices // 2]

if fault in ("unchanged", "altered"):
    import repro.executor as executor
    run = executor.CompiledStencil.run

    def broken(self, grid, steps=None):
        if fault == "unchanged":
            time.sleep(0.05)    # as long as a real call, so the window
            return grid         # holds a real run's number of calls
        out = run(self, grid, steps)
        return out.at[(3,) * out.ndim].add(0.25)

    executor.CompiledStencil.run = broken
elif fault == "no_exchange":
    import repro.core.distributed as distributed
    distributed._exchange_into_ring = lambda padded, *a, **k: padded
elif fault != "none":
    raise SystemExit(f"unknown fault {fault!r}")

result = harness.run(cell, int(seed), float(seconds), traced == "1",
                     t_start=T_START, root=ROOT, require_tpu=False)
harness.report(result)
