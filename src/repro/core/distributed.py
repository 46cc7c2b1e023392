"""Distributed stencil stepper: domain decomposition + deep-halo exchange.

This lifts the paper's overlapped temporal blocking to the cluster level:
instead of exchanging a radius-deep halo every time step (the naive
distributed stencil), shards exchange a ``par_time * halo_radius``-deep halo
once per *superstep* — ``par_time`` time steps per ICI exchange.  The
redundant halo compute is the same overlapped-blocking tax the paper pays
between PEs; the win is a ``par_time``x reduction in collective count (and
latency), which is exactly the paper's "one external-memory round trip per
par_time steps" argument with HBM replaced by ICI.

Halo depth *and* boundary synthesis are derived from the ``StencilProgram``:
the exchange depth comes from the tap set (halo_radius), and the
global-boundary halo is edge-replicated (clamp), wrapped around the mesh via
a cyclic ppermute (periodic), or filled with the boundary value (constant).

Mechanics (per superstep, inside shard_map):
  1. For each decomposed array axis, ``ppermute`` the h-deep boundary strips
     to both neighbors — cyclically for periodic programs, so the wrap halo
     travels the ICI ring instead of being synthesized locally.  The permutes
     per axis are independent of each other *and* of the block interior, so
     XLA's latency-hiding scheduler can overlap them with local compute.
  2. Shards at the global boundary synthesize their missing halo per the
     program's boundary mode; the in-kernel fixup keeps it exact across
     fused time steps (see kernels/common.py).
  3. Run the single-chip temporal-blocked Pallas kernel on the haloed block,
     passing the shard's global origin so boundary fixup happens only at
     physical grid edges.

Multi-superstep runs execute through the *sharded fused run executor*
(:meth:`DistributedStencil.run_fn`): one donated jitted executable whose
``fori_loop`` trip count — the number of full supersteps — is a dynamic
scalar, with the ``steps % par_time`` remainder superstep (shallower
exchange + kernel halo) folded into the tail.  Exactly the single-device
``kernels/common.run_call`` contract lifted onto the mesh: O(1) dispatches
per run, at most one compile per (remainder, decomposition), and the carry
grid updated in place across supersteps.  Grids may carry a leading
``(B, *grid)`` batch axis of independent grids (replicated over the mesh,
sharded spatially), and the local kernel is resolved through the backend
registry so the ``-pipelined`` double-buffered variants run sharded too.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import compat
from repro.core.blocking import BlockPlan
from repro.core.codegen import boundary_pad
from repro.core.program import (ProgramCoeffs, StencilProgram, as_program,
                                normalize_coeffs)
from repro.kernels import common

AxisNames = Tuple[str, ...]


def _repeat_edge(strip: jnp.ndarray, h: int, axis: int) -> jnp.ndarray:
    """Replicate a 1-wide border slab into an h-deep clamp halo."""
    reps = [1] * strip.ndim
    reps[axis] = h
    return jnp.tile(strip, reps)


def exchange_halo(block: jnp.ndarray, axis: int, mesh_axes: AxisNames,
                  h: int, program: StencilProgram, n: int) -> jnp.ndarray:
    """Attach h-deep halos along ``axis``, sourced from mesh neighbors.

    ``n`` is the (static) number of shards along ``mesh_axes`` — threaded in
    from the mesh because the permutation tables must be built at trace time.
    Returns block grown by 2h along ``axis``.  Global-edge shards get halos
    synthesized per the program's boundary mode: clamp-replicated, wrapped
    from the opposite end of the mesh (periodic — the ppermute ring closes),
    or constant-filled.  With a single shard the whole halo is local
    boundary padding.
    """
    if n == 1:
        pads = [(0, 0)] * block.ndim
        pads[axis] = (h, h)
        return boundary_pad(program, block, pads)

    idx = lax.axis_index(mesh_axes)
    periodic = program.boundary == "periodic"

    size = block.shape[axis]
    lo = lax.slice_in_dim(block, 0, h, axis=axis)
    hi = lax.slice_in_dim(block, size - h, size, axis=axis)

    # Send my low strip "left" (to rank-1) so it becomes their high halo;
    # send my high strip "right" (to rank+1) for their low halo.  For
    # periodic programs the ring closes: rank n-1 feeds rank 0.
    if periodic:
        fwd = [(i, (i + 1) % n) for i in range(n)]
        bwd = [((i + 1) % n, i) for i in range(n)]
    else:
        fwd = [(i, i + 1) for i in range(n - 1)]
        bwd = [(i + 1, i) for i in range(n - 1)]
    from_left = lax.ppermute(hi, mesh_axes, fwd)   # my low halo
    from_right = lax.ppermute(lo, mesh_axes, bwd)  # my high halo

    if periodic:
        return jnp.concatenate([from_left, block, from_right], axis=axis)

    # Synthesize the global-boundary halo locally.
    if program.boundary == "constant":
        edge_lo = jnp.full_like(lo, program.boundary_value)
        edge_hi = jnp.full_like(hi, program.boundary_value)
    else:  # clamp
        edge_lo = _repeat_edge(lax.slice_in_dim(block, 0, 1, axis=axis), h,
                               axis)
        edge_hi = _repeat_edge(
            lax.slice_in_dim(block, size - 1, size, axis=axis), h, axis)
    is_first = (idx == 0)
    is_last = (idx == n - 1)
    halo_lo = jnp.where(is_first, edge_lo, from_left)
    halo_hi = jnp.where(is_last, edge_hi, from_right)
    return jnp.concatenate([halo_lo, block, halo_hi], axis=axis)


def _exchange_into_ring(padded: jnp.ndarray, axis: int, mesh_axes: AxisNames,
                        h: int, H: int, nloc: int, periodic: bool,
                        n: int) -> jnp.ndarray:
    """Refresh the halo ring of a *padded* sharded carry over ICI.

    The sharded fused executor keeps each shard's carry in padded layout
    (interior ``[H, H + nloc)`` per sharded axis, ring ``H`` deep), so the
    per-superstep exchange sends only the ``h``-deep interior boundary
    strips (``h`` = the step plan's halo, shallower for the remainder
    superstep) and writes them in place at ring offset ``H - h`` — O(surface)
    over ICI, no concat reallocating the block.  Strips span the full padded
    extent of the other axes, so a later axis' exchange forwards the fresh
    ring data of earlier axes (corner semantics of the old sequential
    concat).  Non-periodic edge shards receive zeros from the open ppermute
    ring; those positions are out-of-grid and healed by the kernel's t=0
    ``boundary_fixup``.

    The strip geometry is :func:`repro.kernels.common.exchange_copies` —
    by SPMD symmetry each copy's ``src`` interval is this shard's own send
    and its ``dst`` interval the landing zone for the neighbor's matching
    send, so the same records drive both this exchange and the
    ``repro.lint.dataflow`` verifier's model of it.
    """
    into_lo, into_hi = common.exchange_copies(axis, h, H, nloc)
    # My *hi* interior strip (into_lo.src) becomes the right neighbor's lo
    # ring; my *lo* strip (into_hi.src) the left neighbor's hi ring.
    hi = lax.slice_in_dim(padded, into_lo.src[0], into_lo.src[1], axis=axis)
    lo = lax.slice_in_dim(padded, into_hi.src[0], into_hi.src[1], axis=axis)
    if periodic:
        fwd = [(i, (i + 1) % n) for i in range(n)]
        bwd = [((i + 1) % n, i) for i in range(n)]
    else:
        fwd = [(i, i + 1) for i in range(n - 1)]
        bwd = [(i + 1, i) for i in range(n - 1)]
    from_left = lax.ppermute(hi, mesh_axes, fwd)   # my low ring
    from_right = lax.ppermute(lo, mesh_axes, bwd)  # my high ring
    padded = lax.dynamic_update_slice_in_dim(padded, from_left,
                                             into_lo.dst[0], axis=axis)
    padded = lax.dynamic_update_slice_in_dim(padded, from_right,
                                             into_hi.dst[0], axis=axis)
    return padded


@dataclasses.dataclass(frozen=True)
class Decomposition:
    """How grid axes map onto mesh axes.

    partition[d] is a tuple of mesh axis names (possibly empty) sharding grid
    axis d.  E.g. 2D on the single-pod mesh: ((("data",), ("model",)));
    multi-pod: ((("pod", "data"), ("model",))).
    """

    partition: Tuple[AxisNames, ...]

    def pspec(self) -> P:
        return P(*[axes if axes else None for axes in self.partition])

    def shards(self, mesh: Mesh, d: int) -> int:
        return math.prod(mesh.shape[a] for a in self.partition[d]) \
            if self.partition[d] else 1


def _local_superstep(block, center, taps, *, program, plan, decomp,
                     axis_shards, global_shape, interpret, nb=0,
                     variant=None):
    """shard_map body: halo exchange + local temporal-blocked kernel.

    ``axis_shards[d]`` is the static shard count along grid axis d; ``nb``
    the number of leading batch axes (0 or 1) riding ahead of the spatial
    dims — batch entries share one exchange (the strips carry the whole
    batch) and one kernel launch (a leading pallas grid dimension).
    """
    h = plan.halo
    offsets = []
    for d in range(program.ndim):
        axes = decomp.partition[d]
        if axes:
            offsets.append(lax.axis_index(axes) * block.shape[nb + d])
        else:
            offsets.append(0)
    offs = jnp.stack([jnp.asarray(o, jnp.int32) for o in offsets])

    haloed = block
    for d in range(program.ndim):
        axes = decomp.partition[d]
        if axes and axis_shards[d] > 1:
            haloed = exchange_halo(haloed, nb + d, axes, h, program,
                                   axis_shards[d])
        else:
            # Unsharded axis: plain boundary padding provides the t=0 halo.
            pads = [(0, 0)] * haloed.ndim
            pads[nb + d] = (h, h)
            haloed = boundary_pad(program, haloed, pads)

    out = common.superstep_call(haloed, center, taps, program, plan,
                                tuple(global_shape), interpret, offs,
                                variant=variant)
    return out


@dataclasses.dataclass
class DistributedStencil:
    """A stencil problem decomposed over a device mesh.

    Direct construction is deprecated (it warns): the unified executor —
    ``repro.stencil(...).compile(grid_shape, steps=..., devices=...)`` —
    resolves the decomposition, builds the mesh, and dispatches here; this
    class remains the sharded executor implementation behind it.

    ``spec`` may be a legacy ``StencilSpec`` or a ``StencilProgram``; the
    exchange depth and boundary synthesis follow the program.

    The *local* kernel is resolved through the backend registry: ``backend``
    pins a registered name (default: the platform's pallas backend), and
    ``variant`` resolves the named kernel-variant sibling ("pipelined"
    resolves the ``-pipelined`` double-buffered lowering; ``pipelined=True``
    is the deprecated bool spelling) — the same resolution rule as the
    unified executor, so every kernel variant that exists on one chip
    exists sharded.  The exception is "temporal": its launch advances
    ``TEMPORAL_CHUNK`` supersteps but the mesh exchanges halos once per
    superstep, so the sharded path refuses it at construction.  Only
    backends declaring ``local_kernel`` traits qualify (``xla-reference``
    pads its own boundaries and cannot consume an exchanged halo).
    """

    spec: object
    coeffs: object
    plan: BlockPlan
    mesh: Mesh
    decomp: Decomposition
    global_shape: Tuple[int, ...]
    interpret: Optional[bool] = None
    backend: Optional[str] = None
    pipelined: bool = False
    variant: Optional[str] = None
    # Internal constructions (the unified executor) pass _warn=False; direct
    # use is deprecated in favor of repro.stencil(...).compile(devices=...).
    _warn: bool = True

    def __post_init__(self):
        from repro.backends import resolve_backend
        if self._warn:
            import warnings
            warnings.warn(
                "constructing DistributedStencil directly is deprecated; "
                "use repro.stencil(program, coeffs=...).compile(grid_shape, "
                "steps=..., devices=<count or shards-per-axis>) — the "
                "unified executor builds the mesh and dispatches to the "
                "same sharded fused executor (DESIGN.md §9)",
                DeprecationWarning, stacklevel=3)
        self.program = as_program(self.spec)
        self.pcoeffs = normalize_coeffs(self.program, self.coeffs)

        name, version, traits = resolve_backend(
            self.backend, self.pipelined, variant=self.variant)
        if traits.variant == "temporal":
            raise ValueError(
                f"RP110: backend {name!r} (the temporally-fused variant) "
                f"cannot run sharded: its launch advances a whole superstep "
                f"chunk per kernel, but the mesh exchanges halos once per "
                f"superstep — the chunk would read neighbor cells that were "
                f"never exchanged (fix: variant='plain' or 'pipelined' on "
                f"the mesh)")
        if not traits.local_kernel:
            raise ValueError(
                f"backend {name!r} cannot serve as the distributed local "
                f"kernel (no local_kernel trait); use a pallas backend")
        self.backend_name = name
        self.backend_version = version
        self.variant = traits.variant
        self.pipelined = traits.variant == "pipelined"
        if self.interpret is None:
            self.interpret = traits.interpret

        for d in range(self.program.ndim):
            n = self.decomp.shards(self.mesh, d)
            if self.global_shape[d] % n != 0:
                raise ValueError(
                    f"grid axis {d} ({self.global_shape[d]}) not divisible by"
                    f" {n} shards")
            local = self.global_shape[d] // n
            if local % self.plan.block_shape[d] != 0:
                raise ValueError(
                    f"local extent {local} on axis {d} not divisible by block"
                    f" {self.plan.block_shape[d]}; shrink the block")
            if local < self.plan.halo:
                raise ValueError(
                    f"halo {self.plan.halo} exceeds local extent {local}; "
                    f"reduce par_time or shards")
        # jitted run executables, keyed by (remainder, batch rank) — the
        # only things that change the traced program (the full-superstep
        # count is a dynamic argument).
        self._exes = {}

    def sharding(self, nb: int = 0) -> NamedSharding:
        """Mesh sharding of the (optionally batched) global grid."""
        return NamedSharding(self.mesh, self._gspec(nb))

    def _gspec(self, nb: int) -> P:
        """PartitionSpec of an nb-batched grid: batch replicated, spatial
        axes per the decomposition."""
        spec = self.decomp.pspec()
        return P(*((None,) * nb), *spec) if nb else spec

    def _mapped_superstep(self, plan: BlockPlan, nb: int):
        """shard_map'd (grid, center, taps) -> grid for one superstep."""
        program, decomp = self.program, self.decomp
        gspec = self._gspec(nb)
        shards = tuple(decomp.shards(self.mesh, d)
                       for d in range(program.ndim))
        body = partial(_local_superstep, program=program, plan=plan,
                       decomp=decomp, axis_shards=shards,
                       global_shape=self.global_shape,
                       interpret=self.interpret, nb=nb,
                       variant=self.variant)
        return compat.shard_map(
            body, mesh=self.mesh,
            in_specs=(gspec, P(), P()),
            out_specs=gspec,
        )

    def superstep_fn(self):
        """Returns a jit-able (grid, center, taps) -> grid superstep."""
        step = self._mapped_superstep(self.plan, 0)

        def stepf(grid, center, taps):
            return step(grid, center, taps)

        return stepf

    def _shards(self) -> Tuple[int, ...]:
        """Shards per grid axis."""
        return tuple(self.decomp.shards(self.mesh, d)
                     for d in range(self.program.ndim))

    def _exchanged(self, d: int) -> bool:
        """True when grid axis ``d`` is split over more than one shard."""
        return bool(self.decomp.partition[d]) and self._shards()[d] > 1

    def _layout(self) -> common.PaddedLayout:
        """The padded carry of one shard in the fused run."""
        ndim = self.program.ndim
        shards = self._shards()
        local = tuple(self.global_shape[d] // shards[d] for d in range(ndim))
        # In-kernel wrap refresh covers device-local periodic axes only;
        # sharded periodic axes wrap through the cyclic ppermute ring.
        # __post_init__ guarantees local % block == 0 and halo <= local, so
        # the layout is never wrap-degenerate here.
        wrap_axes = tuple(
            d for d in range(ndim)
            if self.program.boundary == "periodic" and not self._exchanged(d))
        return common.PaddedLayout(
            halo=self.plan.halo, local_shape=local, rounded=local,
            wrap_axes=wrap_axes,
            align=common.tile_alignment(ndim, not self.interpret,
                                        self.program.dtype))

    def exchange_bytes_per_superstep(self, batch: Optional[int] = None
                                     ) -> int:
        """Bytes one shard sends over ICI in a full superstep of the fused
        run: the operands of :func:`_exchange_into_ring`'s ppermutes, a
        ``plan.halo``-deep strip each way along every split axis
        (:func:`repro.kernels.common.exchange_copies`), over the padded
        extents of the other axes and the ``batch`` grids.  A shard at a
        clamp or constant edge sends one strip of the two on that axis;
        this counts both, as an inner shard sends."""
        layout = self._layout()
        padded = layout.padded_shape
        itemsize = jnp.dtype(self.program.dtype).itemsize * (batch or 1)
        total = 0
        for d in range(self.program.ndim):
            if not self._exchanged(d):
                continue
            depth = sum(c.src[1] - c.src[0] for c in common.exchange_copies(
                d, self.plan.halo, layout.ring[d], layout.local_shape[d]))
            total += depth * itemsize * math.prod(
                n for e, n in enumerate(padded) if e != d)
        return total

    def run_fn(self, rem: int = 0, nb: int = 0):
        """The sharded fused run executor: ONE donated jitted executable
        ``(grid, center, taps, full) -> grid``.

        ``full`` — the number of full supersteps — is a *dynamic* scalar
        (a ``fori_loop`` trip count), so every ``steps = k * par_time + rem``
        with the same remainder reuses one executable; only a distinct
        ``rem`` (a shallower remainder exchange + kernel halo) or batch rank
        compiles again.  The sharded carry is **donated** and lives in
        *padded layout* for the whole run: one pad on entry, one interior
        slice on exit, and per superstep only the ``par_time``-deep halo
        strips cross ICI (written in place into the ring) while the kernel
        ping-pongs between two padded local buffers — no per-superstep
        re-pad or concat re-allocation.  Executables are cached on the
        instance, so repeated
        ``run`` calls are O(1) dispatches with zero retracing — the fix for
        the historical ``run_fn(supersteps)`` that rebuilt (and re-jitted) a
        Python-int-bound loop per call.
        """
        key = (rem, nb)
        fn = self._exes.get(key)
        if fn is not None:
            return fn
        program, decomp, plan = self.program, self.decomp, self.plan
        ndim = program.ndim
        gspec = self._gspec(nb)
        shards, layout = self._shards(), self._layout()
        local, ring = layout.local_shape, layout.ring
        periodic = program.boundary == "periodic"
        interpret, variant = self.interpret, self.variant
        global_shape = tuple(self.global_shape)
        rem_plan = dataclasses.replace(plan, par_time=rem) if rem else None

        def local_body(grid, center, taps, full):
            offsets = []
            for d in range(ndim):
                axes = decomp.partition[d]
                offsets.append(
                    lax.axis_index(axes) * local[d] if axes else 0)
            offs = jnp.stack([jnp.asarray(o, jnp.int32) for o in offsets])
            # Pad ONCE into ring layout; every superstep refreshes only the
            # h-deep strips over ICI and ping-pongs the padded pair.
            src = jnp.pad(grid, [(0, 0)] * nb + [(g, g) for g in ring])
            dst = jnp.zeros_like(src)

            def superstep(carry, step_plan, role="superstep"):
                s, d2 = carry
                h = step_plan.halo
                for dd in range(ndim):
                    axes = decomp.partition[dd]
                    if axes and shards[dd] > 1:
                        s = _exchange_into_ring(s, nb + dd, axes, h,
                                                ring[dd], local[dd],
                                                periodic, shards[dd])
                s2, o = common._padded_superstep_pallas(
                    s, d2, center, taps, program=program, plan=step_plan,
                    layout=layout, global_shape=global_shape,
                    interpret=interpret, role=role, offsets=offs,
                    variant=variant)
                return (o, s2)

            interior = (slice(None),) * nb + tuple(
                slice(ring[d], ring[d] + local[d]) for d in range(ndim))

            def finish(carry):
                if rem_plan is not None:
                    carry = superstep(carry, rem_plan, role="remainder")
                return carry[0][interior]

            # two supersteps per trip keep the pair in its loop slots (as
            # in common.run_call: a swapping body copies both buffers)
            carry = lax.fori_loop(
                0, full // 2,
                lambda _, c: superstep(superstep(c, plan), plan), (src, dst))
            return lax.cond(full % 2 == 1,
                            lambda c: finish(superstep(c, plan)), finish,
                            carry)

        mapped = compat.shard_map(
            local_body, mesh=self.mesh,
            in_specs=(gspec, P(), P(), P()),
            out_specs=gspec,
        )

        def run(grid, center, taps, full):
            common._note_trace("dist_run_call")
            return mapped(grid, center, taps, full)

        fn = jax.jit(run, donate_argnums=(0,))
        self._exes[key] = fn
        return fn

    # Convenience eager wrappers -------------------------------------------

    def superstep(self, grid):
        nb = common.batch_dims(self.program, grid.ndim)
        key = ("superstep", nb)
        fn = self._exes.get(key)
        if fn is None:
            fn = jax.jit(self._mapped_superstep(self.plan, nb))
            self._exes[key] = fn
        return fn(grid, self.pcoeffs.center, self.pcoeffs.taps)

    def run(self, grid, steps: int):
        """Advance ``steps`` time steps: ``steps // par_time`` full
        supersteps plus the folded remainder, in one donated dispatch.
        ``grid`` may carry a leading ``(B, *grid)`` batch axis and is
        consumed (donated) — use the returned array."""
        if steps < 0:
            raise ValueError("steps must be >= 0")
        nb = common.batch_dims(self.program, grid.ndim)
        if steps == 0:
            return grid
        full, rem = divmod(steps, self.plan.par_time)
        fn = self.run_fn(rem, nb)
        with compat.span("launch", grid):
            return fn(grid, self.pcoeffs.center, self.pcoeffs.taps,
                      jnp.asarray(full, jnp.int32))
