"""Share of the traced window in which no op ran on the device: one minus
the union of the device's op intervals over the window, averaged over the
chips."""

from bench import xplane as tr


def reduce(ctx):
    if ctx.trace is None or not ctx.devices:
        return None
    lo, hi = ctx.window
    busy = [tr.busy_ns(ctx.trace, d, ctx.window) for d in ctx.devices]
    if not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
