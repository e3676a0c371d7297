"""Layer-0 hops per query (`QueryStats.hops`, summed over partitions),
over the answers of the traced window."""


def read(ctx):
    q = ctx.stats["queries"]
    return ctx.stats["hops"] / q if q else None
