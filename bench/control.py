#!/usr/bin/env python3
"""Readings that the correctness limits are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --calls N

For each seed, drives ``N`` calls of the cell's traffic through the same
set-up, chain and check as a benchmark run (no timing window), once with the
program as it is (the lower readings) and once with the control in its
place: the plain reference computed in bfloat16, the precision below the
configuration's float32.  The control must come out as not correct.
Prints one JSON line per run.  The benchmark's own runs never run this.
"""

import argparse
import gc
import json
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference_bf16(reference):
    """The plain reference in bfloat16, in the program's place."""
    def make(program_cfg, coeffs, steps, mesh, spans):
        import jax.numpy as jnp
        adv = reference.advance_fn(program_cfg, coeffs, dtype="bfloat16",
                                   mesh=mesh)
        return (lambda g: adv(g.astype(jnp.bfloat16), steps),
                program_cfg["dtype"])
    return make


def readings(cell, seed: int, calls: int, control: bool, *,
             require_tpu=True):
    """The checks of ``calls`` calls from ``seed``: of the program as it is,
    or, with ``control``, of the bfloat16 reference in its place."""
    from bench import harness, spec
    counter = harness.CompileCounter()
    make = reference_bf16(spec.reference(cell.config)) if control else None
    s = harness.setup(cell, seed, require_tpu=require_tpu, make_entry=make)
    w = harness.run_window(s, 0.0, counter, calls=calls)
    s.compiled = None
    gc.collect()
    checks = harness.check(s, w)
    return {"seed": seed,
            "control": "reference-bf16" if control else "sound",
            "calls": w.calls,
            "correct": harness.is_correct(checks), "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--calls", type=int, required=True)
    args = ap.parse_args(argv)

    from bench import harness, spec
    cell = spec.resolve(args.workload, ROOT)
    harness.check_devices(cell, True)
    harness.enable_compile_cache(ROOT)
    for seed in (int(x) for x in args.seeds.split(",")):
        for control in (False, True):
            try:
                out = readings(cell, seed, args.calls, control)
            except Exception as e:
                traceback.print_exc()
                out = {"seed": seed,
                       "control": "reference-bf16" if control else "sound",
                       "error": repr(e)[:500]}
            print(json.dumps(out), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    # the checkout's root (for ``bench``) and ``src`` (for ``repro``), in
    # place of this script's directory
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
