"""Share of device busy time in the commit's ``deaths``, ``compaction`` and
``births`` scopes (%)."""

from bench import subphases


def read(ctx):
    return subphases.busy_share(ctx.reduction, "deaths", "compaction",
                                "births")
