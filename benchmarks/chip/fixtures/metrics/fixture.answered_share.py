"""Test fixture: a per-layer metric added as a file alone."""


def read(ctx):
    recs = ctx.layer_records
    return 100.0 * sum(r.t_done is not None for r in recs) / len(recs)
