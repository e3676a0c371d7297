"""95th percentile of how late the load generator sent requests against
their schedule, over the traced window: a starved generator is not a fast
server."""

import numpy as np


def read(ctx):
    late = [(r.t_sent - r.t_due) * 1e3 for r in ctx.layer_records]
    return float(np.percentile(late, 95)) if late else None
