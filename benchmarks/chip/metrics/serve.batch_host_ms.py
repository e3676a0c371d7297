"""Host time of a batch on the replica thread, in ms: `dispatch` plus
`scatter` less the `device-wait` under them, the median over the batches
flushed in the traced window."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans  # noqa: E402

_spans.trace_on()


def read(ctx):
    return _spans.batch_median(ctx, "host_ms")
