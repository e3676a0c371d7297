"""Latency of every request of the window, from when it was due to when
its answer reached the client, in milliseconds."""

import numpy as np


def percentile(ctx, q: float):
    lat = [(r.t_done - r.t_due) * 1e3 for r in ctx.records
           if r.t_done is not None]
    if len(lat) < len(ctx.records) or not lat:
        return None                   # an unanswered request has no latency
    return float(np.percentile(lat, q))
