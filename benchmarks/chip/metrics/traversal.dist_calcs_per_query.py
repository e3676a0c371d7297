"""Distance evaluations per query (`QueryStats.dist_calcs`, summed over
partitions), over the answers of the traced window; nothing, with a
note, where the backend does not count them."""


def read(ctx):
    q = ctx.stats["queries"]
    calcs = ctx.counter("dist_calcs", "traversal.dist_calcs_per_query")
    return calcs / q if q and calcs is not None else None
