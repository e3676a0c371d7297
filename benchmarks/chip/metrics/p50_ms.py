"""50th percentile of request latency over the window, from when each
request was due (see _latency.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _latency import percentile  # noqa: E402


def read(ctx):
    return percentile(ctx, 50)
