"""Share of the traced window, in percent, that the replica thread spent
in the self time of `hop` and `upper-hop`: the csd host hop planner
(visited bitmap, the host side of the gathers, the descent's lane
bookkeeping), less the store reads, device waits and kernel dispatches
under it. Also logs the replica thread's whole breakdown (_spans.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans  # noqa: E402

_spans.trace_on()


def read(ctx):
    _spans.note_breakdown(ctx)
    return _spans.window_share(ctx, ("hop", "upper-hop"), self_time=True)
