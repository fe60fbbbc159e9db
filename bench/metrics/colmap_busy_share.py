"""Share of device busy time in K1's ``colmap_build`` scope: the block
column map the kernel walks (%)."""

from bench import subphases


def read(ctx):
    return subphases.busy_share(ctx.reduction, "colmap_build")
