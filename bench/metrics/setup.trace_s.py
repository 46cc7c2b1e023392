"""Compilation: host-clock seconds of set-up in which JAX traced the
program to jaxprs (``bench/compile_phases.py``)."""

from bench.compile_phases import total


def reduce(ctx):
    return total(ctx, "trace_s")
