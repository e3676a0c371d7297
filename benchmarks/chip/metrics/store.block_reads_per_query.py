"""Blocks read from the store per query answered over the traced window
(PageCache counters: demand misses and prefetches)."""


def read(ctx):
    q = ctx.stats["queries"]
    if ctx.cache is None or not q:
        return None
    return ctx.cache.get("block_reads", 0) / q
