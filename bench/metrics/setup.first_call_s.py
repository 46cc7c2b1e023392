"""Compilation: host-clock seconds of the warm-up call, blocked on (an
XLA compile or a persistent-cache load, plus one call)."""


def reduce(ctx):
    return ctx.spans.get("first_call")
