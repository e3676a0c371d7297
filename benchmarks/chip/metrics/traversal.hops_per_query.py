"""Layer-0 hops per query (`QueryStats.hops`, summed over partitions),
over the answers of the traced window; nothing, with a note, where the
backend does not count hops."""


def read(ctx):
    q = ctx.stats["queries"]
    hops = ctx.counter("hops", "traversal.hops_per_query")
    return hops / q if q and hops is not None else None
