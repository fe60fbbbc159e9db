"""Share of device busy time in K1's ``k1`` scope: the kernel's launches,
with its operands' packing and its output's unpacking (%)."""

from bench import subphases


def read(ctx):
    return subphases.busy_share(ctx.reduction, "k1")
