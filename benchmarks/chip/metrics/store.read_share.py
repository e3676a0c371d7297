"""Share of the traced window, in percent, that the replica thread spent
in `store-read`: block lookup, block copies through the page cache and
row assembly (`StoreReader.read_rows`, the whole call)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans  # noqa: E402

_spans.trace_on()


def read(ctx):
    return _spans.window_share(ctx, ("store-read",), self_time=False)
