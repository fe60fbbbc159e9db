"""Share of device busy time in the engine's ``neighbor_sweep`` scope (%)."""

from bench import phases


def read(ctx):
    return phases.busy_share(ctx.reduction, "neighbor_sweep")
