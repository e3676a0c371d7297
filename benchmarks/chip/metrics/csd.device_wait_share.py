"""Share of the traced window, in percent, that the replica thread spent
in `device-wait`: blocked on the device at the csd traversal's host syncs
(beam state at each hop's top, the prefetcher's candidates, the descent's
lanes, the final lists) and at the batch's last `block_until_ready`."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans  # noqa: E402

_spans.trace_on()


def read(ctx):
    return _spans.window_share(ctx, ("device-wait",), self_time=False)
