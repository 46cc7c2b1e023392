"""Kernels' share of their roofline: the least time the cell's work could
take on these chips (the larger of flops / VPU f32 peak and bytes / HBM
bandwidth, from ``bench/counts.py``), over the summed device time of the
Pallas kernels in the window, averaged over the chips.  Names the bound."""

from bench import counts, xplane as tr


def reduce(ctx):
    if ctx.trace is None or not ctx.devices:
        return None
    kernel_ns = sum(tr.length(tr.device_ops(ctx.trace, d, ctx.window,
                                            kind="kernel", leaf=True))
                    for d in ctx.devices) / len(ctx.devices)
    if kernel_ns <= 0:
        return None
    bound_s, side = counts.roofline_seconds(
        ctx.counts["flops"], ctx.counts["bytes"], ctx.peak, ctx.chips)
    return {"value": 100.0 * bound_s / (kernel_ns / 1e9), "bound": side}
