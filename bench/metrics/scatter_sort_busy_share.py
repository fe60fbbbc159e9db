"""Share of device busy time in ops whose HLO holds a scatter or a sort (%)."""


def read(ctx):
    s = ctx.reduction.kind_s("scatter", "sort")
    if s <= 0:
        return None
    return 100.0 * s / ctx.reduction.busy_s
