"""Share of device busy time in the engine's ``grid_build`` scope (%)."""

from bench import phases


def read(ctx):
    return phases.busy_share(ctx.reduction, "grid_build")
