"""The plain reference and the inputs from the seed.

The reference imports nothing of the program; here it is checked against
the program's own float64 numpy oracle, an independent implementation,
and its sharded form against itself on one device."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from bench import gen
from bench.references import stencil
from bench.tests.runs import ROOT


def _program(shape, boundary, ndim=2, radius=3):
    return {"ndim": ndim, "radius": radius, "shape": shape,
            "boundary": boundary, "boundary_value": 0.25,
            "dtype": "float32"}


@pytest.mark.parametrize("shape", ["star", "box", "diamond"])
@pytest.mark.parametrize("boundary", ["clamp", "periodic", "constant"])
def test_reference_matches_the_float64_oracle(shape, boundary):
    from repro.core.program import ProgramCoeffs, StencilProgram
    from repro.core.reference import numpy_program_nsteps
    prog = _program(shape, boundary)
    offs = stencil.offsets(shape, 2, 3)
    co = gen.coefficients(offs, seed=7)
    sp = StencilProgram(**prog)
    assert set(sp.neighbor_taps) == set(offs)
    by = dict(co["taps"])
    pc = ProgramCoeffs(center=np.float64(co["center"]),
                       taps=np.array([by[o] for o in sp.neighbor_taps]))
    g = np.asarray(gen.grid((40, 72), "float32", 11))
    got = stencil.advance_fn(prog, co)(jnp.asarray(g), 6)
    want = numpy_program_nsteps(sp, pc, g, 6)
    assert np.max(np.abs(np.asarray(got) - want)) < 1e-6


def test_3d_star_matches_the_float64_oracle():
    from repro.core.program import ProgramCoeffs, StencilProgram
    from repro.core.reference import numpy_program_nsteps
    prog = _program("star", "clamp", ndim=3, radius=4)
    offs = stencil.offsets("star", 3, 4)
    assert len(offs) == 24
    co = gen.coefficients(offs, seed=3)
    sp = StencilProgram(**prog)
    by = dict(co["taps"])
    pc = ProgramCoeffs(center=np.float64(co["center"]),
                       taps=np.array([by[o] for o in sp.neighbor_taps]))
    g = np.asarray(gen.grid((12, 16, 24), "float32", 5))
    got = stencil.advance_fn(prog, co)(jnp.asarray(g), 3)
    assert np.max(np.abs(np.asarray(got) - numpy_program_nsteps(
        sp, pc, g, 3))) < 1e-6


def test_bfloat16_reference_is_far_from_float32():
    prog = _program("star", "clamp", radius=4)
    co = gen.coefficients(stencil.offsets("star", 2, 4), seed=1)
    g = gen.grid((64, 256), "float32", 1)
    f32 = stencil.advance_fn(prog, co)(g, 16)
    bf16 = stencil.advance_fn(prog, co, dtype="bfloat16")(
        g.astype(jnp.bfloat16), 16)
    assert bf16.dtype == jnp.bfloat16
    rel = float(jnp.max(jnp.abs(bf16.astype(jnp.float32) - f32))
                / jnp.max(jnp.abs(f32)))
    assert rel > 1e-3


def test_inputs_from_the_seed():
    a = gen.grid((16, 128), "float32", 2**40 + 3)
    b = gen.grid((16, 128), "float32", 2**40 + 3)
    c = gen.grid((16, 128), "float32", 3)
    assert jnp.array_equal(a, b) and not jnp.array_equal(a, c)
    assert float(a.min()) >= -1.0 and float(a.max()) < 1.0
    co = gen.coefficients(stencil.offsets("star", 2, 4), 2**35)
    assert co == gen.coefficients(stencil.offsets("star", 2, 4), 2**35)
    assert co["center"] == 0.5
    assert sum(c for _, c in co["taps"]) == pytest.approx(0.5, rel=1e-6)
    assert all(c > 0 for _, c in co["taps"])
    rows = gen.receiver_rows(9, 16384, 3)
    assert rows == gen.receiver_rows(9, 16384, 3) and len(set(rows)) == 3
    assert all(0 <= r < 16384 for r in rows)


MESH_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from bench import gen
from bench.references import stencil
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("a0", "a1"))
for shape in ("star", "box"):
    for boundary in ("clamp", "periodic", "constant"):
        prog = dict(ndim=2, radius=3, shape=shape, boundary=boundary,
                    boundary_value=0.25, dtype="float32")
        co = gen.coefficients(stencil.offsets(shape, 2, 3), 4)
        g = gen.grid((64, 96), "float32", 8)
        one = stencil.advance_fn(prog, co)(g, 5)
        sharded = gen.grid((64, 96), "float32", 8,
                           NamedSharding(mesh, P("a0", "a1")))
        four = stencil.advance_fn(prog, co, mesh=mesh)(sharded, 5)
        assert len(four.sharding.device_set) == 4
        assert float(jnp.max(jnp.abs(one - four))) == 0.0, (shape, boundary)
print("OK")
"""


def test_sharded_reference_equals_one_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=ROOT)
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=ROOT)
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-3000:]
