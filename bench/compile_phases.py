"""The program's compile phases during set-up, for the ``setup.*_s``
reducers: ``repro.obs.compile_totals()``, the host-clock seconds JAX spent
inside the program's own spans tracing (``trace_s``), lowering to MLIR with
the Pallas/Mosaic kernels built there (``lower_s``), and compiling each
executable or loading it from the persistent cache (``backend_s``), each
the union of JAX's ``jax.monitoring`` intervals.

The totals are read after the window, which compiles nothing (the harness
counts any executable resolved there), and the reference check runs
outside the program's spans, so they hold the set-up's phases alone.
They are read only where the run was traced on a chip: on a host without
one the kernels go through the Pallas interpreter, another program to
trace, lower and compile.  A program without the counters gives nothing.
"""


def total(ctx, key: str):
    if not ctx.devices:
        return None
    from repro import obs
    totals = getattr(obs, "compile_totals", None)
    return None if totals is None else totals()[key]
