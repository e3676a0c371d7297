"""Mean real rows per batch the server flushed over the traced window
(`ServeStats.batch_sizes`, before bucket padding)."""


def read(ctx):
    n = sum(ctx.batches.values())
    if n == 0:
        return None
    return sum(size * c for size, c in ctx.batches.items()) / n
