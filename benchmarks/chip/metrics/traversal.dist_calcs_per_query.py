"""Distance evaluations per query (`QueryStats.dist_calcs`, summed over
partitions), over the answers of the traced window."""


def read(ctx):
    q = ctx.stats["queries"]
    return ctx.stats["dist_calcs"] / q if q else None
