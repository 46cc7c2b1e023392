"""Plain stencil reference: pad the whole grid by its boundary, sum the taps.

One step is ``out = c0 * g + sum_k c_k * g[x + o_k]`` over the tap offsets
``o_k`` of the configuration's shape (star, box or diamond) and radius, with
out-of-grid reads resolved by ``jnp.pad`` in the boundary's mode.  Nothing
of the system under test is imported: the offsets, coefficients and
boundary handling are worked out here from the configuration alone.

On several devices the same function is jitted with the grid's sharding and
XLA partitions it (its own halo exchange), independent of the program's.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
from jax import lax

PAD_MODE = {"clamp": "edge", "periodic": "wrap", "constant": "constant"}


def offsets(shape: str, ndim: int, radius: int):
    """Every neighbour offset of the stencil, in lexicographic order."""
    out = []
    for off in itertools.product(range(-radius, radius + 1), repeat=ndim):
        nonzero = [abs(c) for c in off if c]
        if not nonzero:
            continue
        if shape == "star" and len(nonzero) != 1:
            continue
        if shape == "diamond" and sum(nonzero) > radius:
            continue
        out.append(off)
    return out


def _pad_kw(program: dict) -> dict:
    if program["boundary"] == "constant":
        return {"constant_values": program.get("boundary_value", 0.0)}
    return {}


def _taps(coeffs: dict, dtype):
    center = jnp.asarray(coeffs["center"], dtype)
    return center, [(tuple(off), jnp.asarray(c, dtype))
                    for off, c in coeffs["taps"]]


def _apply(p, r: int, shape, center, taps):
    """The update of the ``shape``-sized interior of ``p`` (``r`` deep
    halo on every side)."""
    inner = tuple(slice(r, r + n) for n in shape)
    acc = center * p[inner]
    for off, c in taps:
        acc = acc + c * p[tuple(slice(r + o, r + o + n)
                                for o, n in zip(off, shape))]
    return acc


def step_fn(program: dict, coeffs: dict, dtype=None):
    """One time step ``g -> g'`` on one device, in ``dtype`` (default: the
    program's)."""
    dtype = jnp.dtype(dtype or program["dtype"])
    r = program["radius"]
    mode = PAD_MODE[program["boundary"]]
    center, taps = _taps(coeffs, dtype)

    def step(g):
        # pad only along the axes a tap displaces (a star's tap moves along
        # one axis), so no whole-grid temporary is padded on every axis
        padded = {}
        acc = center * g
        for off, c in taps:
            axes = tuple(ax for ax, o in enumerate(off) if o)
            if axes not in padded:
                widths = [(r, r) if ax in axes else (0, 0)
                          for ax in range(g.ndim)]
                padded[axes] = jnp.pad(g, widths, mode=mode,
                                       **_pad_kw(program))
            acc = acc + c * padded[axes][tuple(
                slice(r + o, r + o + n) if ax in axes else slice(None)
                for ax, (o, n) in enumerate(zip(off, g.shape)))]
        return acc

    return step


def _exchange(g, ax: int, name: str, k: int, r: int, program: dict):
    """Extend the local block ``g`` by ``r`` cells on both sides of grid
    axis ``ax``, split over the ``k`` devices of mesh axis ``name``: the
    neighbours' edge strips inside the grid, the boundary outside it."""
    n = g.shape[ax]
    lo = lax.slice_in_dim(g, 0, r, axis=ax)
    hi = lax.slice_in_dim(g, n - r, n, axis=ax)
    periodic = program["boundary"] == "periodic"
    fwd = [(i, (i + 1) % k) for i in range(k if periodic else k - 1)]
    bwd = [((i + 1) % k, i) for i in range(k if periodic else k - 1)]
    from_prev = lax.ppermute(hi, name, fwd)     # my low halo
    from_next = lax.ppermute(lo, name, bwd)     # my high halo
    if not periodic:
        idx = lax.axis_index(name)
        if program["boundary"] == "clamp":
            first = jnp.repeat(lax.slice_in_dim(g, 0, 1, axis=ax), r, ax)
            last = jnp.repeat(lax.slice_in_dim(g, n - 1, n, axis=ax), r, ax)
        else:
            first = last = jnp.full_like(lo, _pad_kw(program)[
                "constant_values"])
        from_prev = jnp.where(idx == 0, first, from_prev)
        from_next = jnp.where(idx == k - 1, last, from_next)
    return lax.concatenate([from_prev, g, from_next], ax)


def sharded_step_fn(program: dict, coeffs: dict, mesh, dtype=None):
    """One time step of the local block inside ``shard_map``: grid axis
    ``d`` is split over mesh axis ``mesh.axis_names[d]``."""
    dtype = jnp.dtype(dtype or program["dtype"])
    r = program["radius"]
    center, taps = _taps(coeffs, dtype)
    names = mesh.axis_names
    sizes = [mesh.shape[n] for n in names]

    def step(g):
        p = g
        for ax, (name, k) in enumerate(zip(names, sizes)):
            if k > 1:
                p = _exchange(p, ax, name, k, r, program)
            else:
                widths = [(0, 0)] * g.ndim
                widths[ax] = (r, r)
                p = jnp.pad(p, widths, mode=PAD_MODE[program["boundary"]],
                            **_pad_kw(program))
        return _apply(p, r, g.shape, center, taps)

    return step


def advance_fn(program: dict, coeffs: dict, dtype=None, mesh=None):
    """Jitted ``(grid, n) -> grid`` after ``n`` steps (``n`` is dynamic,
    so one executable serves every step count).  With ``mesh`` the grid is
    split over it, axis by axis, and the loop runs under ``shard_map``."""
    if mesh is None:
        step = step_fn(program, coeffs, dtype)
        return jax.jit(lambda g, n: lax.fori_loop(
            0, n, lambda _, x: step(x), g))
    from jax.sharding import NamedSharding, PartitionSpec as P
    step = sharded_step_fn(program, coeffs, mesh, dtype)
    spec = P(*mesh.axis_names)
    body = jax.shard_map(
        lambda g, n: lax.fori_loop(0, n, lambda _, x: step(x), g),
        mesh=mesh, in_specs=(spec, P()), out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    return jax.jit(body, in_shardings=(sharding, None),
                   out_shardings=sharding)
