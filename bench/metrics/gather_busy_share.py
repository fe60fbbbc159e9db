"""Share of device busy time in ops whose HLO holds a gather (%)."""


def read(ctx):
    s = ctx.reduction.kind_s("gather")
    if s <= 0:
        return None
    return 100.0 * s / ctx.reduction.busy_s
