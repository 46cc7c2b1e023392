"""The fused executor's own device work (pad-in, ``zeros_like``, interior
slice, ring copies, the caller-side copy): device time of the leaf ops that
are neither Pallas kernels nor collectives, over the device's busy time,
averaged over the chips."""

from bench import xplane as tr


def reduce(ctx):
    if ctx.trace is None or not ctx.devices:
        return None
    shares = []
    for d in ctx.devices:
        busy = tr.busy_ns(ctx.trace, d, ctx.window)
        if busy <= 0:
            return None
        other = tr.length(tr.device_ops(ctx.trace, d, ctx.window,
                                        kind="other", leaf=True))
        shares.append(100.0 * other / busy)
    return sum(shares) / len(shares)
