"""Queries answered per second over the whole window: every answer in it
over all of its time. A closed loop's window runs from one answer to
another, so whole batches fall inside it."""


def read(ctx):
    lo, hi = ctx.window
    if hi <= lo or not ctx.records:
        return None
    return len(ctx.records) / (hi - lo)
