"""Share of demand block accesses served from the PageCache over the
traced window: hits / (hits + misses), in percent."""


def read(ctx):
    if ctx.cache is None:
        return None
    hits, misses = ctx.cache.get("cache_hits", 0), ctx.cache.get(
        "cache_misses", 0)
    return 100.0 * hits / (hits + misses) if hits + misses else None
