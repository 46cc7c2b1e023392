"""Compilation: host-clock seconds of set-up in which the backend compiled
the executables or loaded them from the persistent cache
(``bench/compile_phases.py``)."""

from bench.compile_phases import total


def reduce(ctx):
    return total(ctx, "backend_s")
