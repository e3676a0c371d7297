"""Seconds from the start of the process to the opening of the window:
data, graph build, store write, warm-up and any compilation."""


def read(ctx):
    return ctx.setup_s
