"""95th percentile of the server's queue wait (enqueue to batch flush, the
program's own `QueryResult.queue_ms`) over the traced window."""

import numpy as np


def read(ctx):
    waits = [r.fut.result().queue_ms for r in ctx.layer_records
             if r.fut.done() and r.fut.exception() is None]
    return float(np.percentile(waits, 95)) if waits else None
