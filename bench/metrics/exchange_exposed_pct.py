"""Exchange time that compute does not hide: the time in which a
collective op runs on a device and no other op does, over the window; the
largest over the chips.  Nothing is read where no collective ran."""

from bench import xplane as tr


def reduce(ctx):
    if ctx.trace is None or not ctx.devices:
        return None
    lo, hi = ctx.window
    worst = None
    for d in ctx.devices:
        coll = tr.device_ops(ctx.trace, d, ctx.window, kind="collective",
                             leaf=True)
        if not coll:
            continue
        rest = [iv for kind in ("kernel", "other")
                for iv in tr.device_ops(ctx.trace, d, ctx.window,
                                        kind=kind, leaf=True)]
        share = 100.0 * tr.minus(coll, rest) / (hi - lo)
        worst = share if worst is None else max(worst, share)
    return worst
