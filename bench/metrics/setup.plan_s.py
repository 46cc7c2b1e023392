"""Front door and planner: host-clock seconds of
``repro.stencil(program).compile(...)`` (plan resolution, lint pre-flight,
decomposition; no XLA compile happens there)."""


def reduce(ctx):
    return ctx.spans.get("plan")
