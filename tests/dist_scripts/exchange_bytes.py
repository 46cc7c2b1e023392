"""The exchange bytes the executor reports == the bytes the sharded fused
run's ``collective-permute`` ops carry (4 fake devices).

    python tests/dist_scripts/exchange_bytes.py <case>

Compiles one case through the front door on a mesh, lowers the sharded
``run_fn`` (nothing runs) and prints one JSON line: the executor's
``exchange_bytes_per_superstep`` and the ``repro.compile`` span's
counters, the byte size of every ``collective-permute`` result in the
lowered HLO, and how many full supersteps that HLO holds (two in the
loop body, one in the odd tail).
"""

import _env  # noqa: F401  (sets XLA_FLAGS first)

import json
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro import obs
from repro.core.program import StencilProgram

CASES = {
    "2d_r4_4x1_clamp": (StencilProgram(ndim=2, radius=4), (64, 256),
                        (4, 1), None),
    "2d_r2_box_2x2_periodic": (
        StencilProgram(ndim=2, radius=2, shape="box", boundary="periodic"),
        (64, 256), (2, 2), None),
    "3d_r1_2x2x1_batched": (StencilProgram(ndim=3, radius=1), (32, 32, 128),
                            (2, 2, 1), 2),
}
STEPS = 6
# every case is float32: 4 bytes a cell
PERMUTE = re.compile(r"= f32\[([\d,]*)\]\S* collective-permute\(")

program, shape, devices, batch = CASES[sys.argv[1]]
with obs.profile() as rec:
    cs = repro.stencil(program).compile(shape, steps=STEPS, devices=devices,
                                        batch=batch, cache=False)
(span,) = rec.spans("compile")
dist = cs._dist
nb = 0 if batch is None else 1
grid = jax.device_put(
    jnp.zeros(shape if batch is None else (batch, *shape), jnp.float32),
    dist.sharding(nb))
# rem=0: the loop body's two supersteps and the odd tail's one
text = dist.run_fn(0, nb).lower(
    grid, cs.coeffs.center, cs.coeffs.taps,
    jnp.asarray(3, jnp.int32)).as_text(dialect="hlo")
sizes = [4 * int(np.prod([int(n) for n in dims.split(",") if n]))
         for dims in PERMUTE.findall(text)]
print(json.dumps({"case": sys.argv[1], "decomp": list(cs.decomp),
                  "reported": cs.exchange_bytes_per_superstep,
                  "permute_bytes": sizes, "supersteps_in_hlo": 3,
                  "supersteps_per_call": -(-STEPS // cs.plan.par_time),
                  "span": {k: span.get(k) for k in (
                      "exchange_bytes_per_superstep",
                      "exchanges_per_call")}}))
