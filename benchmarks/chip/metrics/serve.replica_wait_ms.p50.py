"""Median `replica-wait` over the batches flushed in the traced window, in
ms: how long a flushed batch waited in the replica's one-thread executor
before the replica started it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _spans  # noqa: E402

_spans.trace_on()


def read(ctx):
    return _spans.batch_median(ctx, "replica_wait_ms")
