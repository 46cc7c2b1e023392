"""Compilation: host-clock seconds of set-up in which JAX lowered the
program to MLIR, building the Pallas kernels (``bench/compile_phases.py``)."""

from bench.compile_phases import total


def reduce(ctx):
    return total(ctx, "lower_s")
