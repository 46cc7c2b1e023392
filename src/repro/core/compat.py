"""Small helpers over the installed JAX's mesh, shard_map and trace APIs.

* :func:`make_mesh` builds a fully ``Auto`` mesh (``jax.make_mesh``
  defaults to ``Explicit`` axes, which the sharded executor does not use).
* :func:`shard_map` is ``jax.shard_map`` without the varying-manual-axes
  check (the stencil kernels are opaque Pallas calls).
* :func:`tracing` tells host-side instrumentation that it runs inside a
  jax trace; :func:`span` opens a program span only outside one.
"""

from __future__ import annotations

import jax

from repro import obs


def tracing(x) -> bool:
    """True when ``x``, the array argument of an instrumented call, is a
    jax tracer.

    Host-side instrumentation (the ``repro.obs`` spans) must not annotate,
    time, or emit per-run events inside a trace — a jitted wrapper around
    an instrumented entry point would otherwise record trace-time garbage
    once per compile.
    """
    return isinstance(x, jax.core.Tracer)


def span(name: str, x):
    """The ``repro.<name>`` program span (``repro.obs.span``) around work
    on ``x``, or the shared no-op where ``x`` is a tracer."""
    return obs.NULL_SPAN if tracing(x) else obs.span(name)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without replication checking."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
