"""Padded-carry fused executor (ISSUE 6): parity, donation, traffic.

The fused run keeps its carry in halo-extended (padded) layout end-to-end:
a ping-pong pair of donated buffers, the superstep kernel writing its
output tile straight into the destination interior, and the boundary ring
refreshed by O(surface) work (in-kernel wrap DMAs for periodic, per-window
t=0 fixup for clamp/constant) instead of the historical O(volume)
``boundary_pad`` of the whole grid per superstep.

Pins:
  (a) parity with the pre-change executor body (kept verbatim as
      ``common._run_call_padfallback``) and the float64 numpy oracle across
      the radius/ndim/boundary matrix, for plain, pipelined, and batched
      variants;
  (b) the true-shaped carry is donated and the run allocates no third
      grid-sized output buffer (the result aliases a ping-pong buffer);
  (c) O(1) compiles per (remainder, batch rank) survive the rewrite;
  (d) a traffic-regression guard: compiler-counted bytes per superstep stay
      within 1.2x of the ``BlockPlan.run_bytes_per_superstep`` model — so
      the O(volume) re-pad can never silently return — and undercut the
      pre-change executor by >= 1.5x.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import reference as ref
from repro.core.blocking import BlockPlan
from repro.core.codegen import boundary_pad
from repro.core.program import StencilProgram
from repro.kernels import common, ops

TOL = dict(atol=5e-4, rtol=5e-4)
# ulp-level: structurally different executables, XLA:CPU FMA fusion variance
ULP = dict(atol=1e-6, rtol=1e-5)

BLOCKS = {2: (16, 128), 3: (8, 16, 128)}
GRIDS = {2: (37, 150), 3: (9, 18, 140)}     # non-divisible by the blocks


def _legacy_fused_run(g, prog, coeffs, plan, steps):
    """The pre-change executor body — pad the full grid every superstep —
    via the kept fallback implementation, traced exactly as the old
    ``run_call`` did."""
    full, rem = divmod(steps, plan.par_time)
    return common._run_call_padfallback(
        g, coeffs.center, coeffs.taps, full, program=prog, plan=plan,
        true_shape=g.shape, interpret=True, rem=rem, pipelined=False)  # legacy-ok


# ---- (a) parity matrix -----------------------------------------------------

@pytest.mark.parametrize("ndim", [2, 3])
@pytest.mark.parametrize("rad", [1, 2, 3, 4])
@pytest.mark.parametrize("boundary", ["clamp", "periodic", "constant"])
def test_padded_carry_matches_legacy_executor_and_oracle(ndim, rad,
                                                         boundary):
    """steps = 1 full superstep + remainder across the whole matrix: the
    padded-carry executable matches the pre-change pad-per-superstep
    executor at ulp level and the float64 oracle at fp32 tolerance, for the
    plain, pipelined, and batched variants."""
    prog = StencilProgram(ndim=ndim, radius=rad, boundary=boundary,
                          boundary_value=0.25)
    coeffs = prog.default_coeffs(seed=rad)
    plan = BlockPlan(spec=prog, block_shape=BLOCKS[ndim], par_time=2)
    g = ref.random_grid(prog, GRIDS[ndim], seed=rad)
    steps = 3                       # full=1, rem=1

    fused = ops._stencil_run(g, prog, coeffs, plan, steps, interpret=True)
    legacy = _legacy_fused_run(g, prog, coeffs, plan, steps)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(legacy), **ULP)
    want = ref.numpy_program_nsteps(prog, coeffs, g, steps)
    np.testing.assert_allclose(np.asarray(fused), want, **TOL)

    pipe = ops._stencil_run(g, prog, coeffs, plan, steps, interpret=True,
                            pipelined=True)  # legacy-ok
    np.testing.assert_allclose(np.asarray(pipe), np.asarray(fused), **ULP)

    gb = jnp.stack([g, g[tuple(slice(None, None, -1)
                               for _ in range(ndim))]])
    bat = ops._stencil_run(gb, prog, coeffs, plan, steps, interpret=True)
    for i in range(2):
        one = ops._stencil_run(gb[i], prog, coeffs, plan, steps,
                               interpret=True)
        np.testing.assert_allclose(np.asarray(bat[i]), np.asarray(one),
                                   **ULP)


def test_wrap_degenerate_periodic_falls_back_bit_exact():
    """A periodic axis smaller than the layout halo (or the round-up slack)
    cannot host the in-kernel wrap refresh; run_call must route through the
    legacy body and stay bit-identical to it."""
    prog = StencilProgram(ndim=3, radius=2, boundary="periodic")
    plan = BlockPlan(spec=prog, block_shape=BLOCKS[3], par_time=2)
    # axis 0: n=9 rounds to 16 -> hi wrap width 16-9+4 = 11 > 9: degenerate
    lay = common.PaddedLayout(
        halo=plan.halo, local_shape=GRIDS[3],
        rounded=tuple(common.round_up(t, b)
                      for t, b in zip(GRIDS[3], BLOCKS[3])),
        wrap_axes=(0, 1, 2))
    assert lay.wrap_degenerate()
    coeffs = prog.default_coeffs(seed=0)
    g = ref.random_grid(prog, GRIDS[3], seed=0)
    fused = ops._stencil_run(g, prog, coeffs, plan, 4, interpret=True)
    legacy = _legacy_fused_run(g, prog, coeffs, plan, 4)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(legacy))


# ---- (b) donation ----------------------------------------------------------

def test_run_call_donates_true_shaped_carry_batched():
    prog = StencilProgram(ndim=2, radius=1, boundary="clamp")
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    pc = prog.default_coeffs()
    carry = jnp.zeros((2, 20, 140), jnp.float32)
    out = common.run_call(carry, pc.center, pc.taps, 1, program=prog,
                          plan=plan, true_shape=(20, 140), interpret=True,
                          rem=1)
    assert out.shape == (2, 20, 140)
    assert carry.is_deleted()


def test_caller_grid_survives_run():
    """ops._stencil_run copies before donating, so the caller's buffer is
    never consumed and repeated runs on the same array work."""
    prog = StencilProgram(ndim=2, radius=1, boundary="periodic")
    plan = BlockPlan(spec=prog, block_shape=(16, 128), par_time=2)
    coeffs = prog.default_coeffs(seed=1)
    g = ref.random_grid(prog, (32, 128), seed=1)
    a = ops._stencil_run(g, prog, coeffs, plan, 4, interpret=True)
    assert not g.is_deleted()
    b = ops._stencil_run(g, prog, coeffs, plan, 4, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- (c) compile counts ----------------------------------------------------

def test_padded_carry_keeps_o1_compiles():
    prog = StencilProgram(ndim=2, radius=1, boundary="constant",
                          boundary_value=0.5)
    plan = BlockPlan(spec=prog, block_shape=(8, 128), par_time=3)
    coeffs = prog.default_coeffs(seed=2)
    g = ref.random_grid(prog, (25, 131), seed=2)  # shape unique to this test
    common.reset_trace_counts()
    ops._stencil_run(g, prog, coeffs, plan, 3 * 2 + 1, interpret=True)
    assert common.trace_count("run_call") == 1
    ops._stencil_run(g, prog, coeffs, plan, 3 * 7 + 1, interpret=True)
    assert common.trace_count("run_call") == 1      # dynamic full count
    ops._stencil_run(g, prog, coeffs, plan, 3 * 2, interpret=True)
    assert common.trace_count("run_call") == 2      # new remainder
    gb = jnp.stack([g, g])
    ops._stencil_run(gb, prog, coeffs, plan, 3 * 2 + 1, interpret=True)
    assert common.trace_count("run_call") == 3      # new batch rank


# ---- (d) traffic-regression guard ------------------------------------------

_PROBE_PROG = StencilProgram(ndim=2, radius=2, boundary="clamp")
_PROBE_PLAN = BlockPlan(spec=_PROBE_PROG, block_shape=(16, 128), par_time=2)
_PROBE_TRUE = (37, 150)


def _probe_layout():
    rounded = tuple(common.round_up(t, b)
                    for t, b in zip(_PROBE_TRUE, _PROBE_PLAN.block_shape))
    return common.PaddedLayout(halo=_PROBE_PLAN.halo,
                               local_shape=_PROBE_TRUE, rounded=rounded)


def _new_run_unrolled(grid, k):
    """k supersteps of the padded-carry path, UNROLLED so the marginal
    cost_analysis difference k=2 minus k=1 isolates one superstep (a
    fori_loop body is only counted once by the compiler)."""
    coeffs = _PROBE_PROG.default_coeffs(seed=1)
    lay = _probe_layout()
    H = lay.halo
    P = lay.padded_shape
    src = jnp.pad(grid, [(H, P[d] - H - _PROBE_TRUE[d]) for d in range(2)])
    cur = (src, jnp.zeros_like(src))
    for _ in range(k):
        s2, o = common._padded_superstep_pallas(
            cur[0], cur[1], coeffs.center, coeffs.taps,
            program=_PROBE_PROG, plan=_PROBE_PLAN, layout=lay,
            global_shape=_PROBE_TRUE, interpret=True)
        cur = (o, s2)
    return cur[0][tuple(slice(H, H + _PROBE_TRUE[d]) for d in range(2))]


def _old_run_unrolled(grid, k):
    """The pre-change body, unrolled: boundary_pad the whole grid before
    every superstep."""
    coeffs = _PROBE_PROG.default_coeffs(seed=1)
    plan = _PROBE_PLAN
    h = plan.halo
    rounded = tuple(common.round_up(t, b)
                    for t, b in zip(_PROBE_TRUE, plan.block_shape))
    tix = tuple(slice(0, _PROBE_TRUE[d]) for d in range(2))
    pad = [(h, rounded[d] - _PROBE_TRUE[d] + h) for d in range(2)]
    gg = jnp.pad(grid, [(0, rounded[d] - _PROBE_TRUE[d]) for d in range(2)])
    for _ in range(k):
        p = boundary_pad(_PROBE_PROG, gg[tix], pad)
        gg = common._superstep_pallas(p, coeffs.center, coeffs.taps,
                                      _PROBE_PROG, plan, _PROBE_TRUE, True,
                                      None, False)
    return gg[tix]


def _bytes_accessed(fn, g, k):
    cost = jax.jit(fn, static_argnums=1).lower(g, k).compile() \
        .cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return cost.get("bytes accessed")


def test_per_superstep_traffic_within_model_bound(hbm_traffic_only):
    """The guard of ISSUE 6: marginal compiler-counted bytes of one
    superstep must stay within 1.2x of the run_bytes_per_superstep model
    (kernel stream + 2x padded-carry pass-through), counting HBM traffic
    only (``hbm_traffic_only``).  The pre-change
    executor body exceeds that bound on the same probe — the guard has
    teeth — and the new path beats it by >= 1.5x (the acceptance
    criterion)."""
    g = jnp.asarray(np.random.RandomState(0).uniform(-1, 1, _PROBE_TRUE),
                    jnp.float32)
    n1 = _bytes_accessed(_new_run_unrolled, g, 1)
    n2 = _bytes_accessed(_new_run_unrolled, g, 2)
    if n1 is None or n2 is None:
        pytest.skip("compiler does not expose bytes accessed")
    o1 = _bytes_accessed(_old_run_unrolled, g, 1)
    o2 = _bytes_accessed(_old_run_unrolled, g, 2)
    new_marginal = n2 - n1
    old_marginal = o2 - o1
    model = _PROBE_PLAN.run_bytes_per_superstep(_PROBE_TRUE, compiled=False)
    assert new_marginal <= 1.2 * model, (
        f"per-superstep bytes {new_marginal} exceed 1.2x model {model}: "
        f"an O(volume) copy crept back into the fused run")
    assert old_marginal > 1.2 * model, (
        "guard lost its teeth: the pre-change executor body now passes "
        "the model bound")
    assert old_marginal / new_marginal >= 1.5, (
        f"traffic win collapsed: old/new = {old_marginal / new_marginal:.2f}")
